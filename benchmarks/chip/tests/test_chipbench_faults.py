"""The checks that decide ``correct`` catch a broken program: each run
drives the harness with the timed path broken underneath and sees
``correct`` come out false."""
from functools import partial

import jax
import pytest

import chipbench_tiny as tiny
from benchmarks.chip import check, weights


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("chipbench-faults"))


def _broken_step(monkeypatch, fault):
    from repro.launch import train
    original = train.make_train_step

    def make(cfg, rc, opt_cfg):
        good = original(cfg, rc, opt_cfg).__wrapped__

        def step_fn(params, opt_state, batch):
            if fault == "half_batch":  # the mean over the rest
                batch = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            p, o, m = good(params, opt_state, batch)
            if fault == "unchanged":
                return params, opt_state, m
            return p, o, m
        return jax.jit(step_fn)
    monkeypatch.setattr(train, "make_train_step", make)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(bench, monkeypatch, fault):
    _broken_step(monkeypatch, fault)
    result, checks = tiny.run(bench, "rwkv6-1l.steady")
    assert not result["correct"], checks


def test_small_leaf_left_unmoved_is_not_correct(bench, monkeypatch):
    """One of the smallest leaves (bonus_u, a head's bonus, 1/64 of the
    median leaf here) gets no gradient: its gap to the reference is
    measured against the median leaf, and AdamW's update, which does not
    scale with the gradient, still shows it on ``update_gap``."""
    from benchmarks.chip.calibrate import zeroing_leaf
    monkeypatch.setattr(train_module(), "adamw_update",
                        zeroing_leaf(train_module().adamw_update, "bonus_u"))
    result, checks = tiny.run(bench, "rwkv6-1l.steady")
    assert checks["update_gap"][0] > checks["update_gap"][1], checks
    assert not result["correct"]


def train_module():
    from repro.launch import train
    return train


def test_altered_stored_bytes_are_not_correct(bench, monkeypatch):
    from repro.core.store import LibState
    put = LibState.put

    def bad_put(self, path, data):
        if "/data" in path and len(data) > 200:
            data = data[:-1] + bytes([data[-1] ^ 0x01])
        return put(self, path, data)
    monkeypatch.setattr(LibState, "put", bad_put)
    result, checks = tiny.run(bench, "rwkv6-1l.save-full")
    assert checks["readback_mismatch"][0] > 0
    assert not result["correct"]


def test_altered_restore_is_not_correct(bench, monkeypatch):
    from repro.ckpt import checkpoint as C
    restore = C.AssiseCheckpointer.restore

    def bad_restore(self, step=None):
        flat, man = restore(self, step)
        name = sorted(flat)[0]
        flat[name] = flat[name].copy()
        flat[name].reshape(-1)[0] += 1
        return flat, man
    monkeypatch.setattr(C.AssiseCheckpointer, "restore", bad_restore)
    result, checks = tiny.run(bench, "rwkv6-1l.failover")
    assert checks["restore_mismatch"][0] > 0
    assert not result["correct"]


def test_bfloat16_control_fails_the_limits(bench):
    """The reference in bfloat16 in the program's place fails a limit
    that the program keeps (limits from the chip configurations)."""
    import jax.numpy as jnp

    from benchmarks.chip import spec
    from benchmarks.chip.job import Job
    cfg = bench.config("rwkv6-1.6b-1l-v8k.delta")
    job = Job(cfg, bench.traffic("steady"), 5, run_config=tiny.RUN_CONFIG)
    job.build()
    job.run_first_steps(3)
    ref_mod = spec.reference("rwkv6")
    start = partial(weights.make_params, job.shapes, 5)
    batches = [job._batch(i) for i in range(3)]
    ref = ref_mod.train_steps(cfg, start(), batches)
    ctl = ref_mod.train_steps(cfg, start(), batches, dtype=jnp.bfloat16)
    limits = cfg["limits"]
    prog = check.training_numbers(job.first_steps, ref)
    control = check.training_numbers(ctl, ref)
    assert all(v <= limits[k] for k, v in prog.items()), prog
    assert any(v > limits[k] for k, v in control.items()), control
