"""The teacher-forced comparison: it passes whatever tokens the server
chose, and fails when the server computes in a lower precision than the
configuration states or with other weights than the reference is given."""

import asyncio
import tempfile

import jax
import numpy as np
import pytest

from cellbench import check, server, spec
from roots import HERE, REPO

SETTINGS = spec.read_json(HERE / "data" / "settings.json")
MIX = spec.load_module(REPO, "generators", "mix")


def tiny(**serve):
    config = spec.read_json(HERE / "data" / "tiny-dense.json")
    config["serve"] = {**config["serve"], **serve}
    return config


async def served_check(config, seed=5):
    with tempfile.TemporaryDirectory() as work:
        served = await server.start(config, seed, work)
        try:
            return await check.run(served, config, SETTINGS, seed, REPO, MIX)
        finally:
            await served.stop()


@pytest.mark.no_sanitize
def test_check_passes_on_the_stated_precision():
    v = asyncio.run(served_check(tiny()))
    assert v["ok"] and v["pairs"] == 6 * 4 * 20 and v["max"] < 1e-4


@pytest.mark.no_sanitize
def test_check_fails_on_int8_kv():
    v = asyncio.run(served_check(tiny(kv_cache_dtype="int8")))
    assert not v["ok"] and v["median"] > tiny()["check"]["median_tol"]


@pytest.mark.no_sanitize
def test_check_fails_when_one_layer_differs():
    async def flow():
        config = tiny()
        with tempfile.TemporaryDirectory() as work:
            served = await server.start(config, 5, work)
            try:
                good = served.core.params
                bad = {**good, "layers": {
                    **good["layers"],
                    "wo": good["layers"]["wo"].at[1].multiply(1.05)}}
                prompts = check.check_prompts(SETTINGS, 5, 256, MIX)
                answers = await check.collect(served.url, served.name, prompts, 4)
                ref = spec.load_module(REPO, "reference", "dense_gqa")
                fwd = jax.jit(ref.make_forward(config))
                out = {}
                for name, params in (("good", good), ("bad", bad)):
                    d = [x for a in answers for x in check.deltas(
                        a, check.reference_logprobs(fwd, params, a, 4))]
                    out[name] = check.verdict(d, config["check"])
                return out
            finally:
                await served.stop()

    out = asyncio.run(flow())
    assert out["good"]["ok"] and not out["bad"]["ok"]


def test_an_argmax_flip_changes_nothing():
    """The server's tokens need not be the reference's argmax: the context
    the reference is given is the server's own, whatever it chose."""
    config = tiny()
    model = server.resolve(config["model_class"])(server.model_config(config))
    params = model.init_params(server.seed_key(3))
    ref = spec.load_module(REPO, "reference", "dense_gqa")
    fwd = jax.jit(ref.make_forward(config))
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 256, 30).tolist()
    chosen = rng.integers(1, 256, 4).tolist()       # nobody's argmax
    answer = {"prompt": prompt, "tokens": chosen, "top": []}
    lps = check.reference_logprobs(fwd, params, answer, 4)
    assert [int(np.argmax(row)) for row in lps] != chosen
    # a server that reports the true distribution at its own context passes
    answer["top"] = [{int(i): float(row[i]) for i in np.argsort(row)[-20:]}
                     for row in lps]
    assert check.verdict(check.deltas(answer, lps), config["check"])["ok"]
    # and one whose numbers are off by more than the tolerance does not
    answer["top"] = [{k: v + 0.01 for k, v in pos.items()} for pos in answer["top"]]
    assert not check.verdict(check.deltas(answer, lps), config["check"])["ok"]


def test_verdict_rule_share_and_median():
    rule = {"abs_tol": 0.5, "share_within": 0.98, "median_tol": 0.05}
    assert check.verdict([0.01] * 99 + [3.0], rule)["ok"]          # one router swap
    assert not check.verdict([0.01] * 95 + [3.0] * 5, rule)["ok"]  # too many
    assert not check.verdict([0.08] * 100, rule)["ok"]             # shifted median
    assert not check.verdict([], rule)["ok"]
