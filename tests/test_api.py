"""repro.api surface: Session x every registered strategy, MeshSpec,
registry errors, hooks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (LoopHooks, MeshSpec, Session, available_strategies,
                       get_strategy)
from repro.config import ShapeConfig

SHAPE = ShapeConfig("api", 16, 8, "train")


def _flat(tree):
    return np.concatenate([np.asarray(x).ravel()
                           for x in jax.tree.leaves(tree)
                           if jnp.issubdtype(jnp.asarray(x).dtype,
                                             jnp.inexact)])


def _session(strategy, mesh, **kw):
    return Session("flad-vision", strategy=strategy, mesh=mesh,
                   shape=SHAPE, learning_rate=2e-3, **kw)


@pytest.mark.parametrize("strategy,options", [
    ("tensor", {}),
    ("pipeline", {}),
    ("fedavg", {"local_steps": 2}),
    ("fl_pipeline", {"local_steps": 2}),
    ("hier_fl", {"local_steps": 2, "topology": "2@nano*2,agx*2",
                 "codec": "int8"}),
])
def test_session_runs_every_strategy(mesh22, strategy, options):
    ses = _session(strategy, mesh22, **options)
    _, (params0, _) = ses.build()
    before = _flat(params0)
    out = ses.run(2, hooks=LoopHooks(log_fn=lambda *a: None))
    last = out["history"][-1]
    # scalar loss for step strategies; per-client vector (recorded whole,
    # not silently averaged) for the client-stacked round strategies
    loss = last.get("loss", last.get("per_client/loss"))
    assert loss is not None and np.isfinite(loss).all()
    after = _flat(ses.state[0])
    assert not np.allclose(before, after), "params did not change"
    # the merged (flat-model) view exists for every strategy layout
    merged = ses.merged_params()
    assert all(np.all(np.isfinite(x)) for x in jax.tree.leaves(merged)
               if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact))


def test_registry_lists_strategies():
    names = available_strategies()
    for expected in ("tensor", "pipeline", "fedavg", "fl_pipeline",
                     "swift_pipeline", "hier_fl", "async_hier_fl",
                     "distill_fl"):
        assert expected in names


def test_unknown_strategy_raises_with_valid_names():
    with pytest.raises(ValueError) as ei:
        get_strategy("warp-drive")
    msg = str(ei.value)
    for name in available_strategies():
        assert name in msg


def test_meshspec_parse_and_axes():
    spec = MeshSpec.parse("2,4")
    assert spec.dims == (2, 4)
    assert spec.axis_names == ("data", "model")
    spec3 = MeshSpec.parse((2, 2, 2))
    assert spec3.axis_names == ("pod", "data", "model")
    assert MeshSpec(production=True).size == 256
    assert MeshSpec(production=True, multi_pod=True).size == 512
    with pytest.raises(ValueError):
        MeshSpec.parse("2,2,2,2")


def test_meshspec_forces_host_devices_only_on_pinned_cpu(monkeypatch):
    """Off the CPU (here: a platform that is not pinned to cpu) building
    a mesh leaves XLA_FLAGS alone; on the pinned CPU it forces the host
    device count, as before."""
    import os

    from repro.api import mesh as api_mesh
    jax.devices()          # backend up first: the flags below are only read
    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_enable_fast_math=false")
    for pinned, forced in ((False, False), (True, True)):
        monkeypatch.setattr(api_mesh, "_devices_locked", False)
        monkeypatch.setattr(api_mesh, "_cpu_pinned", lambda p=pinned: p)
        MeshSpec((2,)).build()
        flags = os.environ["XLA_FLAGS"]
        assert ("--xla_force_host_platform_device_count=2" in flags) \
            is forced, (pinned, flags)
        assert flags.startswith("--xla_cpu_enable_fast_math=false")


def test_meshspec_larger_than_devices_names_platform_and_count(monkeypatch):
    from repro.api import mesh as api_mesh
    monkeypatch.setattr(api_mesh, "_cpu_pinned", lambda: False)
    have = len(jax.devices())
    with pytest.raises(RuntimeError,
                       match=f"need {have + 1} devices, have {have} cpu"):
        MeshSpec((have + 1,)).build()


def test_compile_cache_env_wins_else_fixed_checkout_path(monkeypatch,
                                                         tmp_path):
    """JAX_COMPILATION_CACHE_DIR is used as set; without it the cache is
    the fixed <checkout>/.jax_cache (a moving path would never hit)."""
    import pathlib

    from repro.launch import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert cc.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.use_compile_cache() == str(cc.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.DEFAULT_DIR)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert cc.DEFAULT_DIR == root / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_session_accepts_concrete_mesh(mesh24):
    ses = _session("tensor", mesh24)
    assert ses.mesh is mesh24
    assert ses.mesh_spec.dims == (2, 4)


def test_hooks_backup_and_history(mesh22):
    from repro.recovery.backup import EdgeBackup
    backup = EdgeBackup(interval=1)
    ses = _session("tensor", mesh22)
    ses.run(2, hooks=LoopHooks(backup=backup, log_fn=lambda *a: None))
    assert backup.backups_taken == 2
    restored, step = backup.restore()
    assert jax.tree.structure(restored) == \
        jax.tree.structure(ses.state[0])


def test_serve_smoke(mesh22):
    ses = Session("flad-adllm", strategy="tensor", mesh=mesh22)
    out = ses.serve(requests=1, batch=2, context=8, decode_steps=2,
                    log_fn=None)
    assert out["total_tokens"] == 2 * 3  # batch x (1 prefill + 2 decode)
    assert out["sequences"][0].shape == (2, 3)


def test_lower_compiles(mesh22):
    ses = _session("tensor", mesh22)
    compiled = ses.lower().compile()
    assert compiled is not None
