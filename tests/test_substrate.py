"""Substrate: checkpointing, data pipeline, optimizer, compression."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data.tokens import DataConfig, TokenPipeline
from repro.training import AdamWConfig, adamw_update, init_opt_state
from repro.training.checkpoint import CheckpointManager
from repro.training.compress import (compress_with_feedback, dequantize_int8,
                                     quantize_int8)


# -- data pipeline ----------------------------------------------------------

def test_pipeline_deterministic():
    pipe = TokenPipeline(DataConfig(vocab=100, seq_len=16, global_batch=4))
    a = pipe.batch_at(7)
    b = pipe.batch_at(7)
    assert np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    c = pipe.batch_at(8)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))


def test_pipeline_labels_are_shifted_tokens():
    pipe = TokenPipeline(DataConfig(vocab=50, seq_len=12, global_batch=2))
    b = pipe.batch_at(0)
    assert b["tokens"].shape == (2, 12)
    assert b["labels"].shape == (2, 12)
    # tokens/labels come from one (T+1) stream: labels[t] == tokens[t+1]
    assert np.array_equal(np.asarray(b["tokens"][:, 1:]),
                          np.asarray(b["labels"][:, :-1]))


def test_pipeline_shard_of_partitions_batch():
    pipe = TokenPipeline(DataConfig(vocab=50, seq_len=8, global_batch=8))
    full = pipe.batch_at(3)
    s0 = pipe.shard_of(3, 0, 4)
    s1 = pipe.shard_of(3, 1, 4)
    assert s0["tokens"].shape == (2, 8)
    assert np.array_equal(np.asarray(s0["tokens"]),
                          np.asarray(full["tokens"][0::4]))
    assert np.array_equal(np.asarray(s1["tokens"]),
                          np.asarray(full["tokens"][1::4]))


# -- optimizer ----------------------------------------------------------------

def test_adamw_decreases_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                      weight_decay=0.0, grad_clip=100.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_opt_state(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    l0 = float(loss(params))
    for _ in range(50):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(cfg, params, g, state)
    assert float(loss(params)) < l0 * 0.05


def test_adamw_grad_clip_caps_update():
    cfg = AdamWConfig(lr=1.0, warmup_steps=0, grad_clip=1e-3,
                      weight_decay=0.0)
    params = {"w": jnp.zeros(3)}
    state = init_opt_state(params)
    g = {"w": jnp.asarray([1e6, -1e6, 1e6])}
    _, _, metrics = adamw_update(cfg, params, g, state)
    assert float(metrics["grad_norm"]) > 1e5  # raw norm reported


# -- checkpoint ---------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    params = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
              "nest": {"b": jnp.ones((4,), jnp.bfloat16)}}
    opt = init_opt_state(params)
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    mgr.save(5, params, opt, {"loss": 1.0})
    mgr.save(10, params, opt, {"loss": 0.5})
    assert mgr.all_steps() == [5, 10]
    p2, o2, man = mgr.restore(10, params, opt)
    assert man["step"] == 10
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        assert np.array_equal(np.asarray(x, np.float32),
                              np.asarray(y, np.float32))
        assert x.dtype == y.dtype


def test_checkpoint_prunes_old(tmp_path):
    params = {"a": jnp.zeros(2)}
    opt = init_opt_state(params)
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, params, opt)
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_atomic_no_partial(tmp_path):
    """A stray .tmp dir (killed writer) must be invisible to latest()."""
    params = {"a": jnp.zeros(2)}
    opt = init_opt_state(params)
    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    mgr.save(1, params, opt)
    os.makedirs(os.path.join(str(tmp_path), "step_0000000002.tmp"))
    assert mgr.latest() == 1


def test_train_resume_replays_identically(tmp_path):
    """kill/restart determinism: train 6 steps straight == 3 + resume 3."""
    from repro.configs import get_smoke_config
    from repro.models import transformer
    from repro.training import build_train_step

    cfg = get_smoke_config("mamba2_130m")
    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=16,
                                    global_batch=2))
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    step_fn = jax.jit(build_train_step(cfg, ocfg))

    def run(params, opt, lo, hi):
        for s in range(lo, hi):
            params, opt, m = step_fn(params, opt, pipe.batch_at(s))
        return params, opt, m

    key = jax.random.PRNGKey(0)
    p0 = transformer.init_params(key, cfg)
    o0 = init_opt_state(p0)
    pA, oA, mA = run(p0, o0, 0, 6)

    pB, oB, _ = run(p0, o0, 0, 3)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, pB, oB)
    pB2, oB2, _ = mgr.restore(3, pB, oB)
    pB3, oB3, mB = run(pB2, oB2, 3, 6)
    assert np.isclose(float(mA["loss"]), float(mB["loss"]), rtol=1e-5)


# -- compression --------------------------------------------------------------

def test_quantize_int8_bounded_error():
    x = jnp.asarray(np.random.default_rng(0).normal(size=512) * 3)
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_error_feedback_is_lossless_over_time():
    """sum of transmitted values converges to sum of true gradients."""
    rng = np.random.default_rng(1)
    err = jnp.zeros(64)
    sent_total = np.zeros(64)
    true_total = np.zeros(64)
    for _ in range(200):
        g = jnp.asarray(rng.normal(size=64))
        q, scale, err = compress_with_feedback(g, err)
        sent_total += np.asarray(dequantize_int8(q, scale))
        true_total += np.asarray(g)
    # residual bounded by one quantization step, not growing with T
    assert np.abs(sent_total - true_total).max() < 0.5


def test_ring_allreduce_matches_psum():
    """ring_allreduce over a k-device mesh == plain sum (subprocess: needs
    multiple devices)."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        import sys
        sys.path.insert(0, "src")
        from repro.training.compress import ring_allreduce
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        x = jnp.arange(4 * 6, dtype=jnp.float32).reshape(4, 6)
        def body(xl):
            return ring_allreduce(xl[0], "dp", 4)[None]
        f = jax.jit(jax.shard_map(body, mesh=mesh,
                                  in_specs=(P("dp", None),),
                                  out_specs=P("dp", None), check_vma=False))
        out = np.asarray(f(x))
        want = np.broadcast_to(x.sum(0), (4, 6))
        assert np.allclose(out, want), (out, want)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "OK" in r.stdout, r.stderr[-2000:]
