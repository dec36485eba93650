"""Device time, in ms per request, of the operations the program put
under one of the named ``jax.named_scope`` scopes, inside the runs of
the named XLA modules in the traced window: the union of their intervals
on the first chip that ran any, from the profiler's trace.  With
``complement``, the modules' device time under none of the scopes.

args: {"scopes": [names], "modules": [name prefixes], "complement": bool}
"""

from .. import profile_rows


def read(args: dict, sources: dict):
    trace = sources["trace"]
    if trace is None or not trace.requests:
        return None
    rows = profile_rows.of(sources)
    if rows is None:
        return None
    lo, hi = trace.window()
    for plane, modules in trace.modules.items():
        ns = profile_rows.scope_ns(
            trace.ops.get(plane, []), rows.op_names, args["scopes"],
            modules, args["modules"], lo, hi, args.get("complement", False),
        )
        if ns is not None:
            return ns / 1e6 / len(trace.requests)
    return None
