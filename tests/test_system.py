"""End-to-end behaviour tests: training loop, checkpoint/restart,
compression, data pipeline, telemetry — the system working together."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.ckpt.manager import CheckpointManager
from repro.configs.registry import get_smoke_config
from repro.data.pipeline import DataConfig, Loader, SyntheticCorpus
from repro.launch import steps as St
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.models import model as Mod
from repro.optim import adamw
from repro.telemetry.stats import StatsCollector, TelemetryConfig
import repro.core as C


def _setup(arch="qwen2-1.5b", steps=60):
    cfg = get_smoke_config(arch)
    mesh = make_host_mesh()
    opt = adamw.OptConfig(total_steps=steps, warmup_steps=3, peak_lr=5e-3)
    return cfg, mesh, opt


def test_training_reduces_loss():
    cfg, mesh, opt = _setup()
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params, _ = Mod.init_model(key, cfg)
        step, sh = St.make_train_step(cfg, opt, mesh, donate=False)
        state = jax.device_put(
            {"params": params, "opt": adamw.init_opt_state(params)}, sh)
        batch = {"tokens": jax.random.randint(key, (4, 32), 0,
                                              cfg.vocab_size)}
        first = last = None
        for i in range(8):
            state, m = step(state, batch)
            first = first if first is not None else float(m["loss"])
            last = float(m["loss"])
    assert last < first * 0.7


def test_microbatch_equivalent_loss():
    cfg, mesh, opt = _setup()
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params, _ = Mod.init_model(key, cfg)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0,
                                              cfg.vocab_size)}
        outs = []
        for mb in (None, 2, 4):
            step, sh = St.make_train_step(cfg, opt, mesh, donate=False,
                                          microbatch=mb)
            st = jax.device_put(
                {"params": params, "opt": adamw.init_opt_state(params)}, sh)
            st, m = step(st, batch)
            outs.append(float(m["loss"]))
    assert abs(outs[0] - outs[1]) < 5e-2 and abs(outs[0] - outs[2]) < 5e-2


def test_checkpoint_save_restore_resume(tmp_path):
    cfg, mesh, opt = _setup()
    key = jax.random.PRNGKey(0)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    with jax.set_mesh(mesh):
        params, _ = Mod.init_model(key, cfg)
        step, sh = St.make_train_step(cfg, opt, mesh, donate=False)
        state = jax.device_put(
            {"params": params, "opt": adamw.init_opt_state(params)}, sh)
        batch = {"tokens": jax.random.randint(key, (4, 32), 0,
                                              cfg.vocab_size)}
        for i in range(3):
            state, m = step(state, batch)
        mgr.save(3, state, blocking=True)
        state, m4 = step(state, batch)  # step 4 result

        restored, rstep = mgr.restore_latest(state, sh)
        assert rstep == 3
        r2, m4b = step(restored, batch)
        assert abs(float(m4b["loss"]) - float(m4["loss"])) < 1e-4


def test_checkpoint_corruption_falls_back(tmp_path):
    cfg, mesh, opt = _setup()
    key = jax.random.PRNGKey(0)
    mgr = CheckpointManager(str(tmp_path), keep=3)
    with jax.set_mesh(mesh):
        params, _ = Mod.init_model(key, cfg)
        state = {"params": params, "opt": adamw.init_opt_state(params)}
        mgr.save(1, state, blocking=True)
        mgr.save(2, state, blocking=True)
    # corrupt the newest checkpoint
    d = os.path.join(str(tmp_path), "step_0000000002")
    victim = sorted(f for f in os.listdir(d) if f.endswith(".npy"))[0]
    with open(os.path.join(d, victim), "r+b") as f:
        f.seek(200)
        f.write(b"\xde\xad\xbe\xef" * 8)
    restored, rstep = mgr.restore_latest(state)
    assert rstep == 1  # fell back to the previous intact checkpoint


def test_keep_k_pruning(tmp_path):
    cfg, mesh, opt = _setup()
    mgr = CheckpointManager(str(tmp_path), keep=2)
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params, _ = Mod.init_model(key, cfg)
        state = {"params": params, "opt": adamw.init_opt_state(params)}
        for s in (1, 2, 3, 4):
            mgr.save(s, state, blocking=True)
    assert mgr.list_steps() == [3, 4]


def test_data_loader_deterministic_and_importance_unbiased():
    dcfg = DataConfig(vocab_size=128, seq_len=16, global_batch=4,
                      n_docs=2000, seed=3)
    corpus = SyntheticCorpus(dcfg)
    l1 = Loader(corpus, dcfg)
    l2 = Loader(corpus, dcfg)
    b1, b2 = l1.batch(7), l2.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])  # replayable
    assert not np.array_equal(l1.batch(8)["tokens"], b1["tokens"])

    li = Loader(corpus, dcfg, importance=True, k=64)
    assert len(li.pool) > 0
    # universal-sample corpus statistics match exact within CV bound
    keys = np.arange(dcfg.n_docs, dtype=np.int32)
    act = np.ones(dcfg.n_docs, bool)
    s = C.universal_monotone_sample(keys, corpus.weights, act, 64, seed=3)
    for f in [C.SUM, C.COUNT, C.thresh(1.0)]:
        est = float(C.estimate(f, corpus.weights, s.prob, s.member))
        ex = float(C.exact(f, corpus.weights, act))
        assert abs(est / ex - 1) < 4 / np.sqrt(63), f.name


def test_telemetry_streaming_queries():
    tel = StatsCollector(TelemetryConfig(k=32, capacity=512))
    rng = np.random.default_rng(0)
    all_w = []
    for step in range(10):
        w = rng.lognormal(0, 1, 100).astype(np.float32)
        keys = step * 1000 + np.arange(100)
        tel.absorb(keys, w)
        all_w.append(w)
    w = np.concatenate(all_w)
    est = tel.query(C.SUM)
    assert abs(est / w.sum() - 1) < 0.5  # k=32 -> CV ~ 0.18; 2.5+ sigma slack
    est_c = tel.query(C.COUNT)
    assert abs(est_c / 1000 - 1) < 0.5


def test_elastic_restart_reshards(tmp_path):
    """Checkpoint on one mesh restores onto a different mesh."""
    cfg, _, opt = _setup()
    key = jax.random.PRNGKey(0)
    mesh1 = make_mesh((1, 1), ("data", "model"))
    mgr = CheckpointManager(str(tmp_path))
    with jax.set_mesh(mesh1):
        params, _ = Mod.init_model(key, cfg)
        state = {"params": params, "opt": adamw.init_opt_state(params)}
        mgr.save(5, state, blocking=True)
    mesh2 = make_host_mesh()  # possibly different shape
    with jax.set_mesh(mesh2):
        step, sh = St.make_train_step(cfg, opt, mesh2, donate=False)
        restored, rstep = mgr.restore_latest(state, sh)
        assert rstep == 5
        batch = {"tokens": jax.random.randint(key, (4, 32), 0,
                                              cfg.vocab_size)}
        _, m = step(restored, batch)
        assert bool(jnp.isfinite(m["loss"]))
