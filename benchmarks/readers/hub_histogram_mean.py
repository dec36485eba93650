"""Mean, in ms, of what a histogram of the metrics hub observed inside
the window: the growth of its _sum over the growth of its _count, all
label sets together, read from the text /metrics would serve.

args: {"metric": "<full name, in seconds>"}
"""


def _total(text: str, series: str) -> float:
    return sum(
        float(line.rsplit(" ", 1)[1])
        for line in text.splitlines()
        if line.startswith(series) and line[len(series)] in " {"
    )


def read(args: dict, sources: dict):
    name = args["metric"]
    grew = {
        part: _total(sources["hub_after"], f"{name}_{part}")
        - _total(sources["hub_before"], f"{name}_{part}")
        for part in ("sum", "count")
    }
    if grew["count"] <= 0:
        return None
    return 1e3 * grew["sum"] / grew["count"]
