"""Fault-tolerance runtime: heartbeats, stragglers, elastic rescale."""
from repro.runtime import (ElasticController, HeartbeatTracker,
                           StragglerDetector)


def test_heartbeat_timeout():
    hb = HeartbeatTracker(timeout=10.0)
    hb.beat(0, 1, now=0.0)
    hb.beat(1, 1, now=0.0)
    hb.beat(0, 2, now=8.0)
    assert hb.sweep(now=11.0) == [1]
    assert hb.alive_workers() == [0]
    # worker returns
    hb.beat(1, 3, now=12.0)
    assert hb.alive_workers() == [0, 1]


def test_heartbeat_backwards_beat_cannot_rewind():
    """A beat stamped by a backwards-jumping clock (NTP step, VM
    migration) proves liveness; it must never rewind ``last_time`` so a
    later honest sweep times the worker out on the skewed stamp."""
    hb = HeartbeatTracker(timeout=10.0)
    hb.beat(0, 1, now=100.0)
    hb.beat(0, 2, now=3.0)          # clock jumped back 97s
    assert hb.sweep(now=105.0) == []
    assert hb.alive_workers() == [0]
    # step counters are monotone under the same skew
    assert hb.workers[0].last_step == 2
    assert hb.workers[0].last_time == 100.0


def test_heartbeat_backwards_sweep_is_clamped():
    """``sweep(t); sweep(t - skew)`` decides exactly what ``sweep(t)``
    alone would: the sweep clock is clamped to its high-water mark, so
    a skewed monitor can neither evict nor resurrect."""
    hb = HeartbeatTracker(timeout=10.0)
    hb.beat(0, 1, now=0.0)
    hb.beat(1, 1, now=20.0)
    assert hb.sweep(now=25.0) == [0]
    # backwards sweep: must not re-evaluate at the earlier time (worker
    # 1 would look alive-forever, worker 0 freshly dead again)
    assert hb.sweep(now=5.0) == []
    assert hb.alive_workers() == [1]
    # and a backwards sweep before any eviction evicts nobody
    hb2 = HeartbeatTracker(timeout=10.0)
    hb2.beat(0, 1, now=50.0)
    hb2.sweep(now=51.0)
    assert hb2.sweep(now=-1000.0) == []
    assert hb2.alive_workers() == [0]


def test_straggler_detection():
    sd = StragglerDetector(window=8, factor=1.5, min_samples=4)
    for step in range(8):
        for w in range(4):
            sd.record(w, 1.0 if w != 2 else 2.5)
    assert sd.stragglers() == [2]


def test_straggler_needs_samples():
    sd = StragglerDetector(min_samples=4)
    sd.record(0, 1.0)
    assert sd.stragglers() == []


def test_elastic_stable():
    ec = ElasticController(model_parallel=16)
    d = ec.decide(16, alive=list(range(16)))
    assert not d.should_rescale


def test_elastic_shrinks_on_failure():
    ec = ElasticController(model_parallel=16)
    d = ec.decide(16, alive=list(range(13)), stragglers=[3])
    assert d.should_rescale
    assert d.new_data_parallel == 8
    assert "shrink" in d.reason


def test_elastic_grows_back():
    ec = ElasticController(model_parallel=16)
    d = ec.decide(8, alive=list(range(16)))
    assert d.should_rescale and d.new_data_parallel == 16


# ---------------------------------------------------------------------------
# persistent compilation cache (entry points call enable_compile_cache)
# ---------------------------------------------------------------------------

_CACHE_SCRIPT = """
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.runtime.compile_cache import enable_compile_cache
print("CACHE_DIR", enable_compile_cache())
print("CONFIG_DIR", jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(7.0)).block_until_ready()
"""


def _run_cache_script(env_dir):
    import os
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], cwd=root,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    return dict(line.split(" ", 1) for line in r.stdout.splitlines()
                if line.startswith(("CACHE_DIR", "CONFIG_DIR")))


def test_compile_cache_uses_env_dir_when_set(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper leaves it alone and the
    compiled program lands there."""
    got = _run_cache_script(str(tmp_path))
    assert got["CACHE_DIR"] == got["CONFIG_DIR"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry written"


def test_compile_cache_defaults_to_repo_dir():
    """Unset: the fixed <repo>/.jax_cache, never a temp or pid path."""
    import os
    from repro.runtime import compile_cache
    got = _run_cache_script(None)
    want = os.path.join(compile_cache._REPO_ROOT, ".jax_cache")
    assert os.path.realpath(got["CACHE_DIR"]) == os.path.realpath(want)
    assert got["CONFIG_DIR"] == got["CACHE_DIR"]
