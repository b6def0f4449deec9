"""Trainer fault tolerance + serving engine integration tests."""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, latest_checkpoint
from repro.configs import get_config
from repro.jaxcompat import make_mesh
from repro.models import Model, ShapeSpec
from repro.models.param import shapes as spec_shapes
from repro.sharding import Partitioner
from repro.serve import Request, ServeConfig, ServeEngine
from repro.train import TrainConfig, Trainer, TrainerConfig
from repro.train.train_step import build_train_artifacts, init_state


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def smoke_model(mesh):
    cfg = get_config("stablelm-3b").smoke()
    return Model(cfg, mesh)


SHAPE = ShapeSpec("t", "train", 32, 4)


def mk_trainer(smoke_model, mesh, tmp, steps=8, **kw):
    return Trainer(
        smoke_model,
        SHAPE,
        Partitioner(mesh),
        TrainConfig(peak_lr=5e-3, warmup=2, total_steps=100),
        TrainerConfig(steps=steps, ckpt_every=4, ckpt_dir=str(tmp), **kw),
    )


def test_loss_decreases(smoke_model, mesh, tmp_path):
    res = mk_trainer(smoke_model, mesh, tmp_path / "a", steps=10).run()
    assert res["steps_run"] == 10
    assert res["history"][-1]["loss"] < res["history"][0]["loss"]
    assert all(np.isfinite(h["loss"]) for h in res["history"])


def test_checkpoint_resume_bitwise(smoke_model, mesh, tmp_path):
    """Run 8 steps straight vs 4 + restart + 4 — final loss must match."""
    r1 = mk_trainer(smoke_model, mesh, tmp_path / "one", steps=8).run()
    t2 = mk_trainer(smoke_model, mesh, tmp_path / "two", steps=4)
    t2.run()
    t3 = mk_trainer(smoke_model, mesh, tmp_path / "two", steps=8)
    r3 = t3.run()
    assert r3["steps_run"] == 4  # resumed from step 4
    assert r1["history"][-1]["loss"] == pytest.approx(r3["history"][-1]["loss"], rel=1e-5)


def test_trainer_recovers_from_transient_failure(smoke_model, mesh, tmp_path):
    t = mk_trainer(smoke_model, mesh, tmp_path / "f", steps=8)
    orig = t.step_fn
    calls = {"n": 0}

    class Flaky:
        def __call__(self, state, batch):
            calls["n"] += 1
            if calls["n"] == 6:
                raise RuntimeError("injected node failure")
            return orig(state, batch)

    t.step_fn = Flaky()
    res = t.run()
    assert res["failures"] == 1
    assert t.step == 8  # finished despite the fault


def test_trainer_straggler_callback_feeds_run_report(smoke_model, mesh, tmp_path):
    """The cluster-scope feedback channel: API-level straggler evidence
    delivered through `trainer.straggler_callback` (the ClusterAdaptive-
    Controller `on_straggler` hook) surfaces in the run result."""
    t = mk_trainer(smoke_model, mesh, tmp_path / "s", steps=4)
    t.straggler_callback(
        "host:7:rank3", "ust_repro", "train_step", 2.7, "2.70x cluster median"
    )
    res = t.run()
    assert res["steps_run"] == 4
    reps = res["straggler_reports"]
    assert len(reps) == 1
    assert reps[0].source == "host:7:rank3" and reps[0].api == "train_step"
    assert reps[0].ratio == pytest.approx(2.7)
    # the wall-clock EWMA channel still reports through the same watchdog
    assert res["straggler_steps"] == t.watchdog.slow_steps


def test_trainer_gives_up_after_max_failures(smoke_model, mesh, tmp_path):
    t = mk_trainer(smoke_model, mesh, tmp_path / "g", steps=8, max_failures=1)

    def always_fail(state, batch):
        raise RuntimeError("permanent failure")

    t.step_fn = always_fail
    with pytest.raises(RuntimeError, match="permanent"):
        t.run()


def test_checkpointer_integrity_detects_corruption(tmp_path):
    ck = Checkpointer(str(tmp_path))
    tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((3, 3))}}
    path = ck.save(5, tree)
    # corrupt one leaf
    target = os.path.join(path, "b__c.npy")
    arr = np.load(target)
    arr[0, 0] = 777.0
    np.save(target, arr)
    with pytest.raises(ValueError, match="integrity"):
        ck.restore(path, tree)


def test_checkpointer_retention(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.zeros(2)}
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    names = sorted(os.listdir(tmp_path))
    assert names == ["step_3", "step_4"]
    assert latest_checkpoint(str(tmp_path)).endswith("step_4")


def test_checkpointer_async(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save_async(1, {"a": jnp.arange(4.0)}, extra={"data": {"step": 1}})
    ck.wait()
    restored, man = ck.restore(latest_checkpoint(str(tmp_path)), {"a": jnp.zeros(4)})
    np.testing.assert_allclose(np.asarray(restored["a"]), np.arange(4.0))
    assert man.extra["data"]["step"] == 1


def test_microbatch_accumulation_matches_full_batch(mesh):
    """grad-accum over 2 microbatches ≈ one full-batch step."""
    cfg = get_config("stablelm-3b").smoke()
    model = Model(cfg, mesh)
    part = Partitioner(mesh)
    t_full = TrainConfig(peak_lr=1e-3, warmup=0, total_steps=10, microbatches=1)
    t_micro = TrainConfig(peak_lr=1e-3, warmup=0, total_steps=10, microbatches=2)
    step_f, *_ = build_train_artifacts(model, part, SHAPE, t_full)
    step_m, *_ = build_train_artifacts(model, part, SHAPE, t_micro)
    state_f = init_state(model, t_full, jax.random.PRNGKey(0))
    state_m = init_state(model, t_micro, jax.random.PRNGKey(0))
    from repro.data import SyntheticPipeline

    batch = {k: jnp.asarray(v) for k, v in next(SyntheticPipeline(model, SHAPE)).items()}
    sf, mf = step_f(state_f, batch)
    sm, mm = step_m(state_m, batch)
    assert float(mf["loss"]) == pytest.approx(float(mm["loss"]), rel=1e-4)
    wf = jax.tree_util.tree_leaves(sf["params"])[0]
    wm = jax.tree_util.tree_leaves(sm["params"])[0]
    np.testing.assert_allclose(np.asarray(wf), np.asarray(wm), rtol=1e-3, atol=1e-5)


def test_grad_compression_step_still_learns(mesh, tmp_path):
    cfg = get_config("stablelm-3b").smoke()
    model = Model(cfg, mesh)
    t = Trainer(
        model,
        SHAPE,
        Partitioner(mesh),
        TrainConfig(peak_lr=5e-3, warmup=2, total_steps=100, grad_compression=True),
        TrainerConfig(steps=8, ckpt_every=100, ckpt_dir=None),
    )
    res = t.run()
    assert res["history"][-1]["loss"] < res["history"][0]["loss"]


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def test_engine_batched_decode(smoke_model):
    params = smoke_model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(smoke_model, params, ServeConfig(batch_slots=3, cache_len=40, max_new_tokens=6))
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, smoke_model.cfg.vocab_size, size=(10,))) for _ in range(5)]
    done = eng.run_until_drained()
    assert len(done) == 5
    assert all(len(r.out_tokens) == 6 for r in done)
    assert all(0 <= t < smoke_model.cfg.vocab_size for r in done for t in r.out_tokens)


@pytest.mark.parametrize(
    "arch", ["stablelm-3b", "mamba2-1.3b", "whisper-medium", "granite-4.0-h-small"]
)
def test_engine_matches_sequential_decode(arch, mesh):
    """Batched engine output for one request == naive prefill+decode loop:
    a model with route counts, models without, and an audio model whose
    frames the engine makes (zeros)."""
    model = Model(get_config(arch).smoke(), mesh)
    params = model.init(jax.random.PRNGKey(0))
    V = model.cfg.vocab_size
    prompt = np.arange(16) % V  # a whole number of SSD chunks
    eng = ServeEngine(model, params, ServeConfig(batch_slots=2, cache_len=40, max_new_tokens=4))
    r = eng.submit(prompt)
    eng.run_until_drained()
    # naive reference
    specs = spec_shapes(model.batch_specs(ShapeSpec("t", "prefill", 16, 1)), model.cfg.dtype)
    batch = {k: jnp.zeros(s.shape, s.dtype) for k, s in specs.items()}
    batch["tokens"] = jnp.asarray(prompt[None], jnp.int32)
    logits, cache, _ = jax.jit(lambda p, b: model.prefill(p, b, 40))(params, batch)
    toks = [int(jnp.argmax(logits[0, 0, :V]))]
    step = jax.jit(model.decode_step)
    for _ in range(3):
        logits, cache, _ = step(params, cache, {"token": jnp.asarray([toks[-1]], jnp.int32)})
        toks.append(int(jnp.argmax(logits[0, 0, :V])))
    assert r.out_tokens == toks
