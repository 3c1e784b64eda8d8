"""Share of the device's busy time spent under given scopes of the model.

Busy time is the self time of the ``XLA Ops`` events (an operation's
duration less what its children cover), summed over the devices.  An
operation's scope is the outermost ``jax.named_scope`` of the model on its
name-stack path, which the profile keeps on the operation's metadata
(``tf_op``): ``embed``, ``attn_proj``, ``attn``, ``attn_out``, ``mlp``,
``logits``, ``sample``.  ``scopes`` lists the scopes to add up; an empty list
stands for the operations under none of them — what XLA puts around the
layer scan (per-layer weight slices, layout copies, the loop itself).  None
when no operation carries a scope: a program from before PR 25, or a backend
whose profile has no operation metadata (the CPU, in rehearsals)."""

from cellbench import spec


def read(ctx: dict, args: dict):
    tracefile = spec.load_module(ctx["root"], "readers", "tracefile")
    t = tracefile.for_run(ctx)
    if not t or not t["scoped"]:
        return None
    by = tracefile.scope_seconds(t)
    total = sum(by.values())
    if not total:
        return None
    return 100.0 * sum(by.get(s, 0.0) for s in args["scopes"] or [""]) / total
