"""Record the golden schedule fixtures of this directory anew.

    python tests/lint_fixtures/proto/record.py

A replay token is a list of indices into DetLoop's ready list, and what
stands on that list at each step is asyncio's own business: 3.12 rewrote
``wait_for`` on ``asyncio.timeout`` (no inner task, fewer callbacks), so
a token recorded under 3.11 names choices that 3.12 never offers.  A
token is good for one minor version of Python; each file says which
(``recorded_with``), and this script is how the next one is met.

Only ``token`` and ``recorded_with`` are written.  ``name``, ``note`` and
``expect`` are the file's own: the scenario, seed and bug variant are
read from the old token, a ``*_pass`` file gets that seed's clean run and
a ``*_violate`` file the first violating run of the bug variant's sweep,
and a run that does not give exactly the file's ``expect`` stops the
script — the fixtures are the plane's negative controls, and an
expectation is never edited to fit a schedule.
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2]))

from dynamo_tpu.analysis.protocheck import (  # noqa: E402
    SCENARIOS,
    decode_token,
    explore_scenario,
    first_violation,
    replay_token,
    run_one,
)


def record(path: Path) -> None:
    doc = json.loads(path.read_text())
    old = decode_token(doc["token"])
    scenario = SCENARIOS[old["scenario"]]
    bug = old.get("bug")
    if bug is None:
        run = run_one(scenario, old["seed"])
    else:
        run = first_violation(explore_scenario(scenario, bug=bug))
        if run is None:
            raise SystemExit(f"{path.name}: {bug} no longer violates")
    got = {"outcome": run.outcome,
           "violations": sorted({v for v, _ in run.violations})}
    if got != doc["expect"]:
        raise SystemExit(f"{path.name}: {got} != {doc['expect']}")
    if replay_token(run.token).trace != run.trace:
        raise SystemExit(f"{path.name}: token does not replay")
    doc["token"] = run.token
    doc["recorded_with"] = "%d.%d" % sys.version_info[:2]
    path.write_text(json.dumps(doc, indent=2) + "\n")
    print(f"{path.name}: seed {run.seed}, {len(run.choices)} choices")


if __name__ == "__main__":
    for p in sorted(HERE.glob("*.json")):
        record(p)
