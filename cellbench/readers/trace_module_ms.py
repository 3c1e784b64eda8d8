"""Mean duration on the device of one program's executions in the traced
slice: the events of the ``XLA Modules`` line whose name (the jitted
entrypoint's module, ``jit__multi_impl(<program id>)``) matches ``pattern``,
mean over executions and devices, in milliseconds.  This is the program from
its first operation to its last, without launch and readback: what an engine
step would take with no host in the way.  None where the profile has no
module line (the CPU, in rehearsals) or no execution matched."""

import re

from cellbench import spec


def read(ctx: dict, args: dict):
    tracefile = spec.load_module(ctx["root"], "readers", "tracefile")
    t = tracefile.for_run(ctx)
    if not t:
        return None
    rx = re.compile(args["pattern"])
    durations = [dur for dev in t["devices"].values()
                 for name, _, dur in dev["modules"] if rx.search(name)]
    return sum(durations) / len(durations) / 1e6 if durations else None
