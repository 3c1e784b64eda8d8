"""Share of the device's busy time spent under one ``jax.named_scope`` of
the model, at whatever depth it sits on an operation's name-stack path
(readers/scope_roofline.py finds the time; readers/trace_scope_share.py
reads only the outermost of the scopes tracefile.py lists).  None where no
operation is under the scope."""

from cellbench import spec


def read(ctx: dict, args: dict):
    finder = spec.load_module(ctx["root"], "readers", "scope_roofline")
    sec = finder.scope_seconds(ctx, args["scope"])
    if not sec or not sec[0] or not sec[1]:
        return None
    return 100.0 * sec[0] / sec[1]
