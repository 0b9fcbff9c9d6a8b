"""The compile ledger of the recompile watcher (monitoring/runtime.py):
one ``compile``/``build`` event a program jax builds, with its outcome
against the persistent cache, the seconds of its three stages and its
start and end on ``time.perf_counter()``; and the serving engine's
``health()["setup"]["started_at"]``, the stamp on the same clock.

The ledger's own builds run in a subprocess with a compile cache of its
own, so that no other test's programs are in the cache it reads and none
of these land in another's."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from deeplearning4j_tpu.serving import GenerationEngine
from deeplearning4j_tpu.zoo import TextGenerationTransformer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = r"""
import json, sys, threading, time
import jax, jax.numpy as jnp
cache = sys.argv[1]
if cache:
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
else:
    jax.config.update("jax_enable_compilation_cache", False)
from deeplearning4j_tpu import monitoring
from deeplearning4j_tpu.monitoring import runtime
from deeplearning4j_tpu.monitoring.events import global_event_log
monitoring.ensure_started()
reg = monitoring.global_registry()
out = {}

def ledger(fn):
    return [e.attrs for e in global_event_log().tail(category="compile")
            if e.name == "build" and e.attrs["fn"] == fn]

def _ledger_probe(a):
    return jnp.tanh(a) * 3 + 1

x3, x5 = jnp.ones(3), jnp.ones(5)
f = jax.jit(_ledger_probe)
a = time.perf_counter(); f(x3).block_until_ready(); b = time.perf_counter()
out["cold"] = {"around": [a, b], "records": ledger("_ledger_probe")}
if not cache:
    print(json.dumps(out)); sys.exit(0)
jax.clear_caches()
f(x3).block_until_ready()
out["warm"] = ledger("_ledger_probe")

c = reg.get(runtime.COMPILE_COUNTER)
def _retrace_probe(a):
    return a * 2
g = jax.jit(_retrace_probe)
before = c.value(fn="_retrace_probe")
g(x3); g(x5); g(x3)
out["retrace"] = {"counted": c.value(fn="_retrace_probe") - before,
                  "records": len(ledger("_retrace_probe"))}

def _inner(a):
    time.sleep(0.3)       # while traced only
    return jnp.sin(a)
inner = jax.jit(_inner)
def _outer(a):
    return inner(a) + 1
out["nested"] = {"outer": (jax.jit(_outer)(x3), ledger("_outer"))[1],
                 "inner": ledger("_inner")}

def _slow_a(a):
    time.sleep(0.3)
    return jnp.cos(a) - 1
def _slow_b(a):
    time.sleep(0.3)
    return jnp.exp(a) + 2
fa, fb = jax.jit(_slow_a), jax.jit(_slow_b)
fa(x3).block_until_ready()          # into the cache: the thread loads it
jax.clear_caches()
gate = threading.Barrier(2)
def run(fn):
    gate.wait()
    fn(x3).block_until_ready()
ts = [threading.Thread(target=run, args=(fn,)) for fn in (fa, fb)]
[t.start() for t in ts]; [t.join() for t in ts]
out["threads"] = {"a": ledger("_slow_a")[-1], "b": ledger("_slow_b")[-1]}

builds = reg.get(runtime.BUILDS_COUNTER)
hist = reg.get(runtime.COMPILE_SECONDS)
out["counters"] = {
    "compiles": reg.get(runtime.COMPILE_COUNTER).total(),
    "builds": builds.total(),
    "records": len(global_event_log().tail(category="compile")),
    "loaded": builds.value(outcome="loaded"),
    "stages": {s: hist.count(stage=s)
               for s in ("trace", "lower", "compile", "load")}}
print(json.dumps(out))
"""


def _probe(cache: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", PROBE, cache], cwd=ROOT,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cached(tmp_path_factory):
    return _probe(str(tmp_path_factory.mktemp("compile_cache")))


@pytest.fixture(scope="module")
def uncached():
    return _probe("")


def test_a_cold_build_reads_compiled_and_a_rebuild_reads_loaded(cached):
    [cold] = cached["cold"]["records"]
    assert cold["outcome"] == "compiled"
    assert [r["outcome"] for r in cached["warm"]] == ["compiled", "loaded"]


def test_with_the_cache_off_a_build_reads_uncached(uncached):
    [cold] = uncached["cold"]["records"]
    assert cold["outcome"] == "uncached"


@pytest.mark.parametrize("stage", ["trace_s", "lower_s", "backend_s"])
def test_each_stage_takes_time(cached, stage):
    for record in cached["warm"]:
        assert record[stage] > 0


def test_start_and_end_lie_inside_the_call_on_perf_counter(cached):
    a, b = cached["cold"]["around"]
    [r] = cached["cold"]["records"]
    assert a <= r["start"] < r["end"] <= b
    assert r["trace_s"] + r["lower_s"] + r["backend_s"] \
        <= r["end"] - r["start"] + 1e-6


def test_an_inner_jit_traced_inside_an_outer_one_is_counted_once(cached):
    """The inner function sleeps 0.3 s while it is traced: the outer
    program's trace holds it once, and the inner builds no program."""
    [outer] = cached["nested"]["outer"]
    assert cached["nested"]["inner"] == []
    assert 0.3 <= outer["trace_s"] < 0.6
    assert outer["trace_s"] + outer["lower_s"] + outer["backend_s"] \
        <= outer["end"] - outer["start"] + 1e-6


def test_two_threads_building_at_once_each_get_their_own_outcome(cached):
    a, b = cached["threads"]["a"], cached["threads"]["b"]
    assert a["start"] < b["end"] and b["start"] < a["end"]   # overlapped
    assert (a["outcome"], b["outcome"]) == ("loaded", "compiled")
    for r in (a, b):
        assert 0.3 <= r["trace_s"] < 0.6


def test_the_compile_counter_counts_what_it_counted_before(cached):
    """One count a program built, loaded or compiled: a new shape
    builds, a shape seen before does not, and a load counts too."""
    assert cached["retrace"] == {"counted": 2, "records": 2}
    assert len(cached["warm"]) == 2
    c = cached["counters"]
    assert c["compiles"] == c["builds"] == c["records"]


def test_the_seconds_histogram_splits_by_stage(cached):
    stages = cached["counters"]["stages"]
    assert stages["trace"] == stages["lower"] == \
        stages["compile"] + stages["load"]
    assert stages["load"] == cached["counters"]["loaded"] >= 2


def _net():
    return TextGenerationTransformer(vocab_size=12, embed_dim=16, n_heads=2,
                                     n_layers=1, max_length=16,
                                     positional="rope").init()


def test_the_engine_stamps_its_start_once():
    eng = GenerationEngine(_net(), 12, slots=2)
    try:
        assert eng.health()["setup"] == {"started_at": None}
        a = time.perf_counter()
        eng.start()
        b = time.perf_counter()
        stamp = eng.health()["setup"]["started_at"]
        assert a <= stamp <= b
        eng.start()
        assert eng.health()["setup"]["started_at"] == stamp
        h = eng.submit([1, 2, 3], steps=2, top_k=1,
                       rng=np.random.default_rng(0))
        h.result(timeout=120)
        assert eng.health()["setup"]["started_at"] == stamp
    finally:
        eng.shutdown()
