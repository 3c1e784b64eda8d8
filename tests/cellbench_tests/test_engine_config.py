"""The EngineConfig the harness builds from a configuration's ``serve``
block equals the one ``dynamo-tpu run`` builds from the same flags."""

import dataclasses

import pytest

from cellbench import server, spec
from roots import REPO

CONFIGS = sorted(p.stem for p in (REPO / "cellbench" / "configs").glob("*.json"))


class Stop(Exception):
    pass


def cli_engine_config(monkeypatch, args):
    """Run cli._build_local_engine up to the EngineCore it would build."""
    import dynamo_tpu.engine as engine
    from dynamo_tpu import cli
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.utils import compilation_cache

    seen = {}

    def fake_core(model, params, cfg, **kw):
        seen["cfg"] = cfg
        raise Stop

    monkeypatch.setattr(ModelDeploymentCard, "from_hf_dir",
                        classmethod(lambda cls, path, name=None: cls(name="x")))
    monkeypatch.setattr(compilation_cache, "enable_persistent_cache", lambda: None)
    monkeypatch.setattr(cli, "_require_tpu", lambda: None)
    monkeypatch.setattr(cli, "_load_any_checkpoint",
                        lambda path, dtype: (object(), object(), False))
    monkeypatch.setattr("dynamo_tpu.utils.mesh.build_mesh", lambda *a, **k: None)
    monkeypatch.setattr(engine, "EngineCore", fake_core)
    with pytest.raises(Stop):
        cli._build_local_engine(args)
    return seen["cfg"]


@pytest.mark.parametrize("name", CONFIGS)
def test_engine_config_parity(monkeypatch, name):
    serve = spec.read_json(REPO / "cellbench" / "configs" / f"{name}.json")["serve"]
    args = server.run_args(serve)
    ours = server.engine_config(args)
    theirs = cli_engine_config(monkeypatch, args)
    assert dataclasses.asdict(ours) == dataclasses.asdict(theirs)
    for key, value in serve.items():
        if hasattr(ours, key):
            assert getattr(ours, key) == value


def test_every_engine_config_field_the_cli_sets_is_set_here():
    """A flag added to ``run`` later must not be dropped in silence: the
    namespace's engine flags all reach server.engine_config."""
    import inspect

    from dynamo_tpu import cli

    theirs = inspect.getsource(cli._build_local_engine)
    ours = inspect.getsource(server.engine_config)
    fields = [f.name for f in dataclasses.fields(type(server.engine_config(
        server.run_args({}))))]
    for f in fields:
        assert (f"{f}=" in theirs) == (f"{f}=" in ours), f
