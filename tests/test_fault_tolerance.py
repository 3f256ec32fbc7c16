"""Checkpoint/restart, corruption fallback, bitwise resume, watchdog."""
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.train.checkpoint import (load_checkpoint, restore_latest,
                                    save_checkpoint)

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": {"c": np.ones(5, np.int32)}}
    save_checkpoint(tmp_path, 3, tree)
    like = {"a": np.zeros((3, 4), np.float32), "b": {"c": np.zeros(5, np.int32)}}
    out, step = restore_latest(tmp_path, like)
    assert step == 3
    np.testing.assert_array_equal(out["a"], tree["a"])


def test_corrupt_checkpoint_skipped(tmp_path):
    tree = {"a": np.arange(4, dtype=np.float32)}
    save_checkpoint(tmp_path, 1, tree)
    save_checkpoint(tmp_path, 2, {"a": tree["a"] * 2})
    newest = sorted(tmp_path.glob("step-*"))[-1]
    raw = (newest / "arrays.msgpack").read_bytes()
    (newest / "arrays.msgpack").write_bytes(raw[: len(raw) // 2])
    out, step = restore_latest(tmp_path, {"a": np.zeros(4, np.float32)})
    assert step == 1
    np.testing.assert_array_equal(out["a"], tree["a"])


def test_retention_gc(tmp_path):
    for s in range(6):
        save_checkpoint(tmp_path, s, {"a": np.zeros(2)}, keep=2)
    assert len(list(tmp_path.glob("step-*"))) == 2


def test_bitwise_resume(tmp_path):
    """Train 6 steps straight vs 3 + checkpoint + restore + 3: identical
    parameters bit for bit (pipeline cursor is part of the state)."""
    from helpers import tiny
    from repro.data.pipeline import TokenPipeline
    from repro.launch.mesh import local_mesh
    from repro.models import init_params
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.train_loop import make_train_step

    cfg = tiny("dense")
    toks = (np.arange(20000) * 7919) % 250
    opt = AdamWConfig(learning_rate=1e-3)

    def fresh():
        params = init_params(cfg, jax.random.PRNGKey(0))
        return params, init_opt_state(params, opt), \
            TokenPipeline(toks, global_batch=4, seq_len=32, seed=5)

    step_fn = make_train_step(cfg, local_mesh(), opt=opt, global_batch=4,
                              donate=False)

    params, state, pipe = fresh()
    for s in range(6):
        params, state, _ = step_fn(params, state,
                                   {"tokens": pipe.global_batch_array(s)})
    straight = params

    params, state, pipe = fresh()
    for s in range(3):
        params, state, _ = step_fn(params, state,
                                   {"tokens": pipe.global_batch_array(s)})
    save_checkpoint(tmp_path, 3, {"params": params, "opt": state})
    like = {"params": params, "opt": state}
    restored, _ = restore_latest(tmp_path, like)
    params, state = restored["params"], restored["opt"]
    for s in range(3, 6):
        params, state, _ = step_fn(params, state,
                                   {"tokens": pipe.global_batch_array(s)})
    for a, b in zip(jax.tree_util.tree_leaves(straight),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_watchdog_restart_end_to_end(tmp_path):
    """Fault injection: crash mid-run, watchdog respawns, training reaches
    the target step and reports a final loss."""
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3_1_7b",
           "--smoke", "--steps", "16", "--batch", "2", "--seq-len", "32",
           "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
           "--crash-at", "7", "--watchdog", "--log-every", "5"]
    env = {"PYTHONPATH": f"{REPO}/src", "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", str(REPO)),
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=600)
    assert "train.fault_injection" in out.stdout
    assert "train.resume" in out.stdout
    assert "train.done" in out.stdout, \
        out.stdout[-2000:] + out.stderr[-2000:]
