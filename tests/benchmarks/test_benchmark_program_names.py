"""The readers of the program's own names (PR 25): device time by
``jax.named_scope`` scope and the chip's idle time by the program span
that covers it, on hand-written rows; and each of their metric files
found by name like every other."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import profile_rows, spec, xplane  # noqa: E402
from benchmarks.readers import (  # noqa: E402
    span_median, xplane_idle_under_spans, xplane_scope_time,
)

NEW = [
    "commit_assemble_ms", "commit_judge_ms", "sha512_device_ms",
    "decompress_device_ms", "scalar_mul_device_ms", "final_check_device_ms",
    "kernel_rest_device_ms", "idle_verifier_host_ms", "idle_validation_ms",
    "idle_device_wait_ms", "idle_handoff_ms",
]
BENCH = spec.load_benchmark()

# one run of the verify module, 100..1000, and a stray module after it.
# The while of scalar_mul (300..800) holds its iterations' operations,
# which lie inside it and must not count twice; 100..120 has no scope.
MODULES = [(100.0, 1000.0, "jit_verify_batch(123)"),
           (1100.0, 1200.0, "jit_convert(9)")]
OPS = [
    (100.0, 120.0, "%copy.1 = copy()"),
    (120.0, 200.0, "%fusion.1 = fusion()"),
    (200.0, 300.0, "%fusion.2 = fusion()"),
    (300.0, 800.0, "%while.9 = while()"),
    (300.0, 500.0, "%fusion.3 = fusion()"),
    (520.0, 790.0, "%fusion.3 = fusion()"),
    (800.0, 900.0, "%fusion.4 = fusion()"),
    (900.0, 1000.0, "%fusion.5 = fusion()"),
    (1100.0, 1200.0, "%fusion.6 = fusion()"),
]
P = "jit(verify_batch)/jit(main)/"
OP_NAMES = {
    "jit_verify_batch(123)": {
        "copy.1": "",
        "fusion.1": P + "sha512/while/body/add",
        "fusion.2": P + "decompress/mul",
        "while.9": P + "scalar_mul/while",
        "fusion.3": P + "scalar_mul/while/body/closed_call/add",
        "fusion.4": P + "final_check/eq",
        "fusion.5": P + "scalar_prep/and",
    },
    # another program's instruction of the same name is not this one's
    "jit_convert(9)": {"fusion.6": "jit(convert)/sha512/add",
                       "fusion.5": "jit(convert)/scalar_mul/add"},
}
VERIFY = ["jit__device_verify", "jit_verify_batch"]


def scope_ns(scopes, **kw):
    return profile_rows.scope_ns(OPS, OP_NAMES, scopes, MODULES, VERIFY,
                                 0.0, 2000.0, **kw)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message from (number, int | bytes | str) pairs."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def test_op_names_are_read_from_the_hlo_the_profiler_stored():
    """/host:metadata holds, per program, an event metadata entry named
    like the XLA Modules event whose stat "Hlo Proto" is the HloProto;
    the loader reads it from the file's bytes."""
    def instr(name, op_name=None):
        meta = [(7, _msg((1, "add"), (2, op_name)))] if op_name else []
        return (2, _msg((1, name), (2, "add"), *meta))

    hlo = _msg((1, _msg(
        (1, "jit_verify_batch"),
        (3, _msg((1, "body"), instr("fusion.3", P + "scalar_mul/while/body/add"),
                 instr("tuple.1"))),
        (3, _msg((1, "main"), instr("while.9955", P + "scalar_mul/while"),
                 instr("fusion.7", P + "sha512/" + "x" * 300))),
    )))
    plane = _msg(
        (1, 7), (2, "/host:metadata"),
        (5, _msg((1, 1), (2, _msg((1, 1), (2, "other stat"))))),
        (5, _msg((1, 2), (2, _msg((1, 2), (2, "Hlo Proto"))))),
        (4, _msg((1, 11), (2, _msg(
            (1, 11), (2, "jit_verify_batch(123)"),
            (5, _msg((1, 1), (6, b"not an hlo"))),
            (5, _msg((1, 2), (6, hlo))))))),
    )
    device = _msg((1, 8), (2, "/device:TPU:0"), (3, b"\x12\x03abc" * 1000))
    got = profile_rows.module_op_names(memoryview(
        _msg((1, device), (1, plane), (4, "host"))))
    assert got == {"jit_verify_batch(123)": {
        "fusion.3": P + "scalar_mul/while/body/add", "tuple.1": "",
        "while.9955": P + "scalar_mul/while",
        "fusion.7": P + "sha512/" + "x" * 300,
    }}
    assert profile_rows.module_op_names(memoryview(_msg((1, device)))) == {}


def test_an_operation_is_joined_to_its_instruction_by_name():
    assert profile_rows.instruction(
        "%while.9955 = (s32[]{:T(128)}, s32[22,256]{1,0}) while(%tuple.3), "
        "condition=%cond, body=%body") == "while.9955"
    assert profile_rows.instruction("%fusion.1 = fusion()") == "fusion.1"
    assert profile_rows.instruction("op") == "op"


def test_intersect_keeps_what_both_cover():
    a = [(0.0, 10.0), (20.0, 30.0), (40.0, 50.0)]
    b = [(5.0, 25.0), (28.0, 45.0)]
    assert profile_rows.intersect(a, b) == [
        (5.0, 10.0), (20.0, 25.0), (28.0, 30.0), (40.0, 45.0)]
    assert profile_rows.intersect(a, []) == []
    assert profile_rows.total(profile_rows.intersect(a, a)) == 30.0


def test_a_scope_is_a_component_of_the_path_and_not_a_substring():
    assert profile_rows.under(P + "scalar_mul/while/body/add", ["scalar_mul"])
    assert profile_rows.under(P + "scalar_mul/comb_lookup_a/mul", ["comb_lookup_a"])
    assert not profile_rows.under(P + "scalar_mul_old/add", ["scalar_mul"])
    assert not profile_rows.under("", ["sha512"])


def test_scope_time_counts_a_while_and_its_iterations_once():
    assert scope_ns(["scalar_mul"]) == 500.0  # not 500 + 200 + 270
    assert scope_ns(["sha512"]) == 80.0  # the other module's sha512 is outside
    assert scope_ns(["decompress", "var_table"]) == 100.0
    assert scope_ns(["final_check"]) == 100.0


def test_scope_time_is_clipped_to_the_window():
    assert profile_rows.scope_ns(
        OPS, OP_NAMES, ["scalar_mul"], MODULES, VERIFY, 400.0, 700.0) == 300.0


def test_complement_is_the_modules_time_under_none_of_the_scopes():
    four = ["sha512", "decompress", "var_table", "scalar_mul", "final_check"]
    rest = scope_ns(four, complement=True)
    assert rest == 20.0 + 100.0  # the unnamed copy and scalar_prep
    parts = [scope_ns(s) for s in (["sha512"], ["decompress", "var_table"],
                                   ["scalar_mul"], ["final_check"])]
    assert sum(parts) + rest == xplane.module_ns(MODULES, VERIFY, 0.0, 2000.0)


def test_a_program_without_the_names_gives_nothing_and_does_not_raise():
    bare = {"jit_verify_batch(123)": {
        name: "jit(verify_batch)/jit(main)/add"
        for name in OP_NAMES["jit_verify_batch(123)"]}}
    for names in (bare, {}):  # no scope in the HLO; no HLO in the profile
        for complement in (False, True):
            assert profile_rows.scope_ns(
                OPS, names, ["sha512"], MODULES, VERIFY, 0.0, 2000.0,
                complement=complement) is None


# two requests; the chip runs 130..400 and 620..900.  Host spans, on
# whatever thread: assembly inside commit.verify's hand-over, the wait,
# the judging; 100..110 and 500..505 are under no span of a class.
REQUESTS = [(100.0, 500.0, xplane.REQUEST), (500.0, 1000.0, xplane.REQUEST)]
CHIP = [(130.0, 400.0, "op"), (620.0, 900.0, "op")]
SPANS = [
    (110.0, 125.0, "commit.assemble"),
    (118.0, 128.0, "verify.uncached_assemble"),  # overlaps the assemble span
    (128.0, 470.0, "verify.device_wait"),
    (126.0, 132.0, "verify.h2d_dispatch"),  # overlaps the wait
    (470.0, 500.0, "commit.judge"),
    (505.0, 600.0, "commit.assemble"),
    (600.0, 615.0, "verify.uncached_assemble"),
    (615.0, 960.0, "verify.device_wait"),
    (960.0, 1000.0, "commit.judge"),
    (0.0, 2000.0, "verify.sched.collect"),  # in no class: changes nothing
]
CLASSES = list(xplane_idle_under_spans.CLASSES.values())


def test_idle_goes_to_the_first_class_that_covers_it():
    verifier, validation, wait, handoff = profile_rows.idle_by_class(
        CHIP, REQUESTS, SPANS, CLASSES, 100.0, 1000.0)
    # 118..130 (assemble, then dispatch up to the first operation) and
    # 600..615: the verifier's own host work wins over what it overlaps
    assert verifier == 12.0 + 15.0
    # commit.assemble where no verifier span lies over it, and judging
    assert validation == (118.0 - 110.0) + 30.0 + 95.0 + 40.0
    # only the wait covers: 400..470, 615..620, 900..960
    assert wait == 70.0 + 5.0 + 60.0
    # no span of a class: 100..110 and 500..505
    assert handoff == 10.0 + 5.0


def test_the_classes_add_up_to_the_idle_time_inside_requests():
    by_class = profile_rows.idle_by_class(
        CHIP, REQUESTS, SPANS, CLASSES, 100.0, 1000.0)
    inside = xplane.split_gaps(
        xplane.gaps(CHIP, 100.0, 1000.0), REQUESTS, 100.0, 1000.0
    )["inside_request"]
    assert sum(by_class) == sum(inside) == 350.0
    # idle between requests belongs to no class
    apart = [(100.0, 480.0, xplane.REQUEST), (520.0, 1000.0, xplane.REQUEST)]
    assert sum(profile_rows.idle_by_class(
        CHIP, apart, SPANS, CLASSES, 100.0, 1000.0)) == 350.0 - 40.0


def test_a_program_that_mirrors_no_span_gives_nothing():
    assert profile_rows.idle_by_class(
        CHIP, REQUESTS, [(0.0, 2000.0, "verify.sched.collect")], CLASSES,
        100.0, 1000.0) is None


def sources():
    trace = xplane.Trace(
        ops={"/device:TPU:0": CHIP + [(s, e, "%fusion.1 = fusion()")
                                      for s, e, _ in CHIP]},
        modules={"/device:TPU:0": [(130.0, 400.0, "jit_verify_batch(1)"),
                                   (620.0, 900.0, "jit_verify_batch(1)")]},
        requests=list(REQUESTS),
    )
    rows = profile_rows.Rows(
        op_names={"jit_verify_batch(1)": {"op": "", "fusion.1": P + "sha512/add"}},
        annotations=sorted(SPANS),
    )
    return {"trace": trace, "profile_rows": rows, "spans": []}


def test_readers_report_per_request_in_ms():
    src = sources()
    args = {"scopes": ["sha512"], "modules": VERIFY}
    # 130..400 and 620..900 lie under sha512's fusion
    assert xplane_scope_time.read(args, src) == (270.0 + 280.0) / 1e6 / 2
    assert xplane_scope_time.read(
        dict(args, scopes=["scalar_mul"]), src) is None
    got = {c: xplane_idle_under_spans.read({"class": c}, src)
           for c in ("verifier_host", "validation", "device_wait", "handoff")}
    assert got == {"verifier_host": 27.0 / 2e6, "validation": 173.0 / 2e6,
                   "device_wait": 135.0 / 2e6, "handoff": 15.0 / 2e6}
    assert "idle_by_class" in src["profile_rows"].memo  # computed once


def test_readers_give_nothing_without_requests_or_names():
    src = sources()
    src["trace"].requests = []
    assert xplane_scope_time.read(
        {"scopes": ["sha512"], "modules": VERIFY}, src) is None
    assert xplane_idle_under_spans.read({"class": "handoff"}, src) is None
    src = sources()
    src["profile_rows"] = profile_rows.Rows()  # the parent's profile
    assert xplane_scope_time.read(
        {"scopes": ["sha512"], "modules": VERIFY, "complement": True}, src) is None
    assert xplane_idle_under_spans.read({"class": "handoff"}, src) is None


def test_of_finds_no_profile_where_none_was_written(tmp_path, monkeypatch):
    from benchmarks import harness

    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    assert profile_rows.of({}) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_resolves_by_name_and_reads_what_it_names(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["unit"] == "ms" and entry["moves"] == "verdict_p50_ms"
    for cell_name in entry["workloads"]:
        cell = spec.resolve(cell_name)
        m = next(m for m in cell.per_layer if m["name"] == name)
        reader = spec.module("readers", m["reader"])
        src = sources()
        src["spans"] = [
            {"name": n, "ph": "X", "ts": 0.0, "dur": 250.0}
            for n in ("commit.assemble", "commit.judge")
        ]
        value = reader.read(m["args"], src)
        if reader is span_median:
            assert value == 0.25 and entry["source"] == "program_span"
        elif name in ("sha512_device_ms", "kernel_rest_device_ms") or (
                reader is xplane_idle_under_spans):
            assert value is not None and value >= 0
            assert entry["source"] == "device_trace"
        else:
            assert value is None  # the hand-written rows hold no such scope


def test_the_kernel_metrics_partition_the_verify_program():
    """The four scope metrics and the complement name each scope once
    between them, and the same modules as verify_device_ms."""
    files = {
        n: spec.load_json(os.path.join(
            REPO, "benchmarks", "layer_metrics", n + ".json"))
        for n in NEW + ["verify_device_ms"] if n.endswith("device_ms")
    }
    whole = files.pop("verify_device_ms")
    rest = files.pop("kernel_rest_device_ms")
    assert rest["args"]["complement"] is True
    named = [s for f in files.values() for s in f["args"]["scopes"]]
    assert sorted(named) == sorted(rest["args"]["scopes"])
    assert len(named) == len(set(named))
    for f in list(files.values()) + [rest]:
        assert f["reader"] == "xplane_scope_time"
        assert f["args"]["modules"] == whole["args"]["modules"]


# the comb program: scalar_mul holds nothing but the two lookups and the
# fold, each under a scope nested in it; the fold's while holds its
# iterations' operations
COMB_MODULES = [(0.0, 1000.0, "jit__device_verify(7)")]
Q = "jit(_device_verify)/jit(main)/scalar_mul/"
COMB_NAMES = {"jit__device_verify(7)": {
    "fusion.1": "jit(_device_verify)/jit(main)/decompress/mul",
    "gather.2": Q + "comb_lookup_a/gather",
    "gather.3": Q + "comb_lookup_b/gather",
    "while.4": Q + "tree_reduce/while",
    "fusion.5": Q + "tree_reduce/while/body/add",
}}
COMB_OPS = [
    (0.0, 240.0, "%fusion.1 = fusion()"),
    (240.0, 310.0, "%gather.2 = gather()"),
    (310.0, 340.0, "%gather.3 = gather()"),
    (340.0, 900.0, "%while.4 = while()"),
    (350.0, 600.0, "%fusion.5 = fusion()"),
    (610.0, 890.0, "%fusion.5 = fusion()"),
]


def test_fold_and_lookups_add_up_to_scalar_mul():
    """tree_reduce_device_ms and comb_lookup_device_ms read scopes nested
    in scalar_mul, on the comb program only, and split it in two."""
    args = {
        n: spec.load_json(os.path.join(
            REPO, "benchmarks", "layer_metrics", n + ".json"))["args"]
        for n in ("scalar_mul_device_ms", "tree_reduce_device_ms",
                  "comb_lookup_device_ms")
    }
    ns = {
        n: profile_rows.scope_ns(COMB_OPS, COMB_NAMES, a["scopes"],
                                 COMB_MODULES, a["modules"], 0.0, 1000.0)
        for n, a in args.items()
    }
    assert ns["tree_reduce_device_ms"] == 560.0
    assert ns["comb_lookup_device_ms"] == 100.0
    assert ns["scalar_mul_device_ms"] == 660.0
    assert args["tree_reduce_device_ms"]["modules"] == ["jit__device_verify"]
    # the uncached program has no such scope: nothing to read there
    a = args["tree_reduce_device_ms"]
    assert profile_rows.scope_ns(OPS, OP_NAMES, a["scopes"], MODULES,
                                 a["modules"], 0.0, 2000.0) is None


@pytest.mark.parametrize("name", ["tree_reduce_device_ms", "comb_lookup_device_ms"])
def test_nested_scope_metric_is_listed_with_the_scope_that_holds_it(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    holder = next(m for m in BENCH["per_layer"]
                  if m["name"] == "scalar_mul_device_ms")
    assert entry["workloads"] == holder["workloads"]
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) == (
        holder["unit"], holder["layer"], holder["moves"], holder["source"])
    for cell_name in entry["workloads"]:
        cell = spec.resolve(cell_name)
        assert name in [m["name"] for m in cell.per_layer]
