"""The harness's check, driven through a whole run at a size the CPU can
hold with the device guard skipped, comes out false when the timed path
is broken underneath it, and true when it is not."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness, run, serve

HERE = os.path.dirname(os.path.abspath(__file__))


def serve_verdict(root, monkeypatch, broken=None):
    import jax
    if broken is not None:
        orig = serve.Session.new_scheduler

        def new_scheduler(self, cell):
            orig(self, cell)
            self.engine.decode_fn = broken(self.engine.decode_fn)
        monkeypatch.setattr(serve.Session, "new_scheduler", new_scheduler)
    spec = harness.cell_spec("tiny.chat", root)
    devs = jax.devices()[:1]
    out = serve.run(spec, (1 << 31) + 11, 2.0, False, time.perf_counter(), devs)
    out["devs"] = devs
    return run.result(spec, out, False, run.checks_of(spec["cell"],
                                                       out["cmp"]))


def unchanged(decode_fn):
    """The decode step hands back the cache it was given: the new key and
    value never reach the pool."""
    def f(params, cache, token, pos):
        return cache, decode_fn(params, cache, token, pos)[1]
    return f


def altered(decode_fn):
    """Slot 0's token is changed where it is produced."""
    def f(params, cache, token, pos):
        cache, logits = decode_fn(params, cache, token, pos)
        top = logits[0].argmax()
        return cache, logits.at[0, (top + 1) % logits.shape[1]].set(
            logits[0, top] + 1.0)
    return f


def test_serve_sound_run_is_correct(tiny_root, cpu_peaks, monkeypatch):
    r = serve_verdict(tiny_root, monkeypatch)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert r["checks"]["max_gap"]["value"] <= r["checks"]["max_gap"]["limit"]


@pytest.mark.parametrize("fault", [unchanged, altered])
def test_serve_fault_is_caught(tiny_root, cpu_peaks, monkeypatch, fault):
    assert not serve_verdict(tiny_root, monkeypatch, fault)["correct"]


def train_verdict(root, what):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, os.path.join(HERE, "_tiny_train.py"),
                        root, what], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_train_sound_run_is_correct(tiny_root):
    r = train_verdict(tiny_root, "none")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
def test_train_fault_is_caught(tiny_root, fault):
    r = train_verdict(tiny_root, fault)
    assert not r["correct"], r["checks"]
