"""tpulint (deeplearning4j_tpu/analysis): per-rule positive/negative
fixtures, inline suppressions, baseline round-trip, CLI contract, and the
self-scan gate that keeps the repo clean beyond the committed baseline."""

import json
import os
import textwrap
from pathlib import Path

import pytest

from deeplearning4j_tpu.analysis import baseline as bl
from deeplearning4j_tpu.analysis.cli import main
from deeplearning4j_tpu.analysis.core import scan_file, scan_paths
from deeplearning4j_tpu.analysis.rules import ALL_RULES, RULES_BY_ID

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "deeplearning4j_tpu"


def _scan_snippet(tmp_path, source, name="mod.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return scan_file(str(p), ALL_RULES, root=str(tmp_path))


def _rules_of(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------
# rule: host-sync-in-hot-loop
# ---------------------------------------------------------------------
class TestHostSyncRule:
    def test_positive_float_and_block_in_per_batch_path(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            class Net:
                def _fit_batch(self, ds):
                    loss = self.step(ds)
                    self.score = float(loss)
                    jax.block_until_ready(self.params)
        """)
        assert _rules_of(fs) == ["host-sync-in-hot-loop"] * 2

    def test_positive_item_and_device_get_in_fit_loop(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            def fit(model, batches):
                for b in batches:
                    loss = model.step(b)
                    print(loss.item())
                    jax.device_get(loss)
        """)
        assert _rules_of(fs) == ["host-sync-in-hot-loop"] * 2

    def test_negative_outside_hot_path_or_loop(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            def fit(model, b):
                loss = model.step(b)      # no loop at this level
                return float(loss)

            def score(model, b):
                return float(model.loss(b))
        """)
        assert fs == []

    def test_negative_module_without_jax_is_exempt(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import numpy as np

            def fit(stats, batches):
                for b in batches:
                    stats.append(float(np.mean(b)))
        """)
        assert fs == []

    def test_negative_benign_scalar_casts_and_host_literals(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax
            import numpy as np

            def _fit_batch(self, ds, seqs):
                n = int(ds.features.shape[0])
                m = float(len(seqs))
                lens = np.asarray([len(s) for s in seqs])
                return n, m, lens
        """)
        assert fs == []


# ---------------------------------------------------------------------
# rule: device-transfer-in-hot-loop
# ---------------------------------------------------------------------
class TestDeviceTransferRule:
    def test_positive_asarray_and_device_put_in_per_batch_path(self,
                                                               tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax
            import jax.numpy as jnp

            class Net:
                def _fit_batch(self, ds):
                    x = jnp.asarray(ds.features)
                    y = jax.device_put(ds.labels)
                    return self.step(x, y)
        """)
        assert _rules_of(fs) == ["device-transfer-in-hot-loop"] * 2

    def test_positive_jnp_array_in_fit_loop(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax.numpy as jnp

            def fit(model, batches):
                for b in batches:
                    model.step(jnp.array(b.features))
        """)
        assert _rules_of(fs) == ["device-transfer-in-hot-loop"]

    def test_negative_outside_hot_path_and_constants(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax
            import jax.numpy as jnp

            def prepare(ds):
                # not a fit/epoch hot path: staging here is fine
                return jnp.asarray(ds.features)

            class Net:
                def _fit_batch(self, ds):
                    pad = jnp.asarray(3)  # literal scalar, not a batch
                    return self.step(ds, pad)

            def fit(model, x):
                x = jax.device_put(x)  # once, before the loop
                for _ in range(3):
                    model.step(x)
        """)
        assert fs == []

    def test_negative_module_without_jax_is_exempt(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            def _fit_batch(self, ds):
                return jnp.asarray(ds.features)
        """)
        assert fs == []

    def test_suppression_and_baseline_cover_jit_boundary_remnants(
            self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax.numpy as jnp

            class Net:
                def _fit_batch(self, ds):
                    # compat path when prefetch is off
                    # tpulint: disable=device-transfer-in-hot-loop
                    x = jnp.asarray(ds.features)
                    return self.step(x)
        """)
        assert fs == []

    def test_positive_per_step_table_rebuild(self, tmp_path):
        """The serving decode-loop shape this rule grew to catch: the
        host rebuilds and re-uploads the full page table every step
        even when nothing changed."""
        fs = _scan_snippet(tmp_path, """
            import jax
            import jax.numpy as jnp

            class Engine:
                def _dispatch_step(self):
                    table = jnp.asarray(self._tables_np())
                    return self._decode(self.pool[table])

                def step(self):
                    t = jax.device_put(self._tables_np())
                    return self._decode(t)
        """)
        assert _rules_of(fs) == ["device-transfer-in-hot-loop"] * 2
        assert any("per-step path" in f.message for f in fs)

    def test_negative_cached_table_path(self, tmp_path):
        """The engine's cached-table fix shape: the transfer lives in a
        cache helper OUTSIDE the per-step names, rebuilt only after an
        invalidating mutation — steady-state steps re-upload nothing."""
        fs = _scan_snippet(tmp_path, """
            import jax.numpy as jnp

            class Engine:
                def _tables_dev(self):
                    if self._cache is None:
                        self._cache = jnp.asarray(self._tables_np())
                    return self._cache

                def _invalidate_tables(self):
                    self._cache = None

                def _dispatch_step(self):
                    return self._decode(self.pool[self._tables_dev()])
        """)
        assert fs == []

    def test_negative_nested_step_is_jit_body(self, tmp_path):
        """A nested ``def step(...)`` is a jitted/scan body — its
        jnp.asarray is a trace-time constant, not a per-step H2D."""
        fs = _scan_snippet(tmp_path, """
            import jax
            import jax.numpy as jnp

            class Net:
                def _get_train_step(self):
                    def step(params, batch):
                        decay = jnp.asarray(self.decay_schedule)
                        return params, decay
                    return jax.jit(step)
        """)
        assert fs == []


# ---------------------------------------------------------------------
# rule: tracer-leak
# ---------------------------------------------------------------------
class TestTracerLeakRule:
    def test_positive_self_assign_in_decorated_jit(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            class M:
                @jax.jit
                def step(self, x):
                    self.cache = x * 2
                    return x
        """)
        assert _rules_of(fs) == ["tracer-leak"]

    def test_positive_global_assign_in_wrapped_fn(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            _LAST = None

            def step(x):
                global _LAST
                _LAST = x * 2
                return x

            fast_step = jax.jit(step)
        """)
        assert _rules_of(fs) == ["tracer-leak"]

    def test_negative_unjitted_function_may_mutate(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            class M:
                def record(self, x):
                    self.cache = x * 2
                    return x
        """)
        assert fs == []


# ---------------------------------------------------------------------
# rule: recompile-hazard
# ---------------------------------------------------------------------
class TestRecompileHazardRule:
    def test_positive_jit_in_loop(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            def run(fns, x):
                for f in fns:
                    y = jax.jit(f)(x)
                return y
        """)
        assert _rules_of(fs) == ["recompile-hazard"]

    def test_positive_list_static_argnums(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            def f(x, n):
                return x * n

            g = jax.jit(f, static_argnums=[1])
        """)
        assert _rules_of(fs) == ["recompile-hazard"]

    def test_positive_branch_on_traced_arg(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """)
        assert _rules_of(fs) == ["recompile-hazard"]

    def test_negative_static_arg_branch_and_none_check(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("train",))
            def f(x, mask, train):
                if train:                 # static: fine
                    x = x * 2
                if mask is None:          # identity check: fine
                    return x
                if x.shape[0] > 4:        # shape metadata: fine
                    return x + 1
                return x
        """)
        assert fs == []

    def test_negative_cached_jit_outside_loop(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            def get_step(cache, fn):
                if "step" not in cache:
                    cache["step"] = jax.jit(fn, static_argnums=(2,))
                return cache["step"]
        """)
        assert fs == []


# ---------------------------------------------------------------------
# rule: jit-key-drift (ISSUE 13 — generalizes PR 11's env-read case)
# ---------------------------------------------------------------------
def _scan_project(tmp_path, files, rules=None):
    """Write a multi-module fixture project and scan it whole-program
    (ProjectInfo built over the directory)."""
    for name, src in files.items():
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return scan_paths([str(tmp_path)], rules=rules, root=str(tmp_path))


class TestJitKeyDriftRule:
    def test_positive_env_read_in_jit_building_step_builder(
            self, tmp_path):
        """ISSUE 11 (migrated from recompile-hazard): os.environ
        resolved inside a step-builder body — the value bakes into the
        trace but sits in no jit key, so a flip keeps the stale
        compiled step (the BENCH_FUSE class)."""
        fs = _scan_snippet(tmp_path, """
            import os
            import jax

            class Net:
                def _get_train_step(self, carry):
                    fused = os.environ.get("MY_FUSE") == "1"

                    def step(p, x):
                        return p * x if fused else p + x

                    return jax.jit(step)
        """)
        assert _rules_of(fs) == ["jit-key-drift"]
        assert "os.environ read inside step-builder" in fs[0].message

    def test_positive_env_read_in_plan_resolution_name(self, tmp_path):
        """Name-matched plan-resolution seams are flagged even when the
        jit construction lives in a helper they call."""
        fs = _scan_snippet(tmp_path, """
            import os

            def resolve_plan(net):
                return os.getenv("MY_PLAN", "xla")
        """)
        assert _rules_of(fs) == ["jit-key-drift"]

    def test_positive_env_subscript_in_step_builder(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import os
            import jax

            def _get_output_fn(net):
                impl = os.environ["MY_IMPL"]
                return jax.jit(lambda x: x)
        """)
        assert _rules_of(fs) == ["jit-key-drift"]

    def test_negative_env_read_outside_builders(self, tmp_path):
        """Env reads at module scope or in ordinary config functions are
        someone else's business — only trace-building bodies retrace."""
        fs = _scan_snippet(tmp_path, """
            import os
            import jax

            DEFAULT_DIR = os.environ.get("MY_DATA_DIR", "/tmp")

            def load_config():
                return os.environ.get("MY_MODE", "prod")

            def get_step(cache, fn):
                return jax.jit(fn)
        """)
        assert fs == []

    def test_positive_mutable_global_unkeyed(self, tmp_path):
        """A set_*-seam module global read in a jit-building body
        without entering the cache key: the trace bakes it in."""
        fs = _scan_snippet(tmp_path, """
            import jax

            _IMPL = "xla"

            def set_impl(v):
                global _IMPL
                _IMPL = v

            def build_step(net):
                impl = _IMPL
                def step(p):
                    return p if impl == "xla" else -p
                return jax.jit(step)
        """)
        assert _rules_of(fs) == ["jit-key-drift"]
        assert "mutable global" in fs[0].message

    def test_negative_mutable_global_in_cache_key(self, tmp_path):
        """The sanctioned pattern (the repo's _STREAM_CACHE_SHARDING
        idiom): the read lands in the jit cache key,
        so flipping the seam retraces instead of staling."""
        fs = _scan_snippet(tmp_path, """
            import jax

            _IMPL = "xla"

            def set_impl(v):
                global _IMPL
                _IMPL = v

            def build_step(net, cache):
                key = ("step", _IMPL)
                if key not in cache:
                    impl = _IMPL  # same global, keyed above: exempt
                    def step(p):
                        return p if impl == "xla" else -p
                    cache[key] = jax.jit(step)
                return cache[key]
        """)
        assert fs == []

    def test_negative_immutable_global_is_config(self, tmp_path):
        """A module constant nobody rebinds via ``global`` is
        configuration, not process-wide mutable state."""
        fs = _scan_snippet(tmp_path, """
            import jax

            _DEFAULT = "xla"

            def build_step(net):
                impl = _DEFAULT
                def step(p):
                    return p if impl == "xla" else -p
                return jax.jit(step)
        """)
        assert fs == []

    def test_positive_cross_module_accessor(self, tmp_path):
        """A builder calling another module's accessor over a mutable
        global: flagged through the project layer."""
        fs = _scan_project(tmp_path, {
            "seam.py": """
                _IMPL = ("xla", False)

                def set_impl(v):
                    global _IMPL
                    _IMPL = (v, False)

                def impl():
                    return _IMPL
            """,
            "net.py": """
                import jax
                from seam import impl

                def _get_decode_fn(net):
                    mode = impl()
                    def step(x):
                        return x
                    return jax.jit(step)
            """,
        })
        assert _rules_of(fs) == ["jit-key-drift"]
        assert "accessor 'impl()'" in fs[0].message

    def test_negative_cross_module_accessor_keyed(self, tmp_path):
        fs = _scan_project(tmp_path, {
            "seam.py": """
                _IMPL = ("xla", False)

                def set_impl(v):
                    global _IMPL
                    _IMPL = (v, False)

                def impl():
                    return _IMPL
            """,
            "net.py": """
                import jax
                from seam import impl

                def _get_decode_fn(net, cache):
                    key = ("decode", impl())
                    if key not in cache:
                        cache[key] = jax.jit(lambda x: x)
                    return cache[key]
            """,
        })
        assert fs == []

    def test_positive_construction_snapshot(self, tmp_path):
        """The PR 10 health-accounting shape: __init__ snapshots a
        process-wide accessor onto self while dispatches follow the
        LIVE setting."""
        fs = _scan_project(tmp_path, {
            "seam.py": """
                _IMPL = "xla"

                def set_impl(v):
                    global _IMPL
                    _IMPL = v

                def impl():
                    return _IMPL
            """,
            "engine.py": """
                from seam import impl

                class Engine:
                    def __init__(self):
                        self._impl = impl()
            """,
        })
        assert _rules_of(fs) == ["jit-key-drift"]
        assert "construction-time snapshot" in fs[0].message

    def test_negative_snapshot_in_owning_module_and_set_call(
            self, tmp_path):
        """The seam's own module wiring its default, and a WRITE through
        the set_* seam, are the documented pattern."""
        fs = _scan_project(tmp_path, {
            "seam.py": """
                _IMPL = "xla"

                def set_impl(v):
                    global _IMPL
                    _IMPL = v

                def impl():
                    return _IMPL

                class Local:
                    def __init__(self):
                        self._impl = impl()
            """,
            "engine.py": """
                from seam import set_impl

                class Engine:
                    def __init__(self, impl_name):
                        set_impl(impl_name)
                        self._impl = impl_name
            """,
        })
        assert fs == []


# ---------------------------------------------------------------------
# rule: donation-use-after-consume (ISSUE 13 — the PR 10 class)
# ---------------------------------------------------------------------
class TestDonationRule:
    DONATING = """
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, x):
            return state + x
    """

    def test_positive_read_after_donate(self, tmp_path):
        fs = _scan_snippet(tmp_path, self.DONATING + """
            def run(state, x):
                out = step(state, x)
                return state + out
        """)
        assert _rules_of(fs) == ["donation-use-after-consume"]
        assert "'state'" in fs[0].message

    def test_positive_redispatch_after_donate(self, tmp_path):
        fs = _scan_snippet(tmp_path, self.DONATING + """
            def run(state, x):
                a = step(state, x)
                b = step(state, x)
                return a, b
        """)
        assert _rules_of(fs) == ["donation-use-after-consume"]

    def test_positive_self_attr_chain(self, tmp_path):
        fs = _scan_snippet(tmp_path, self.DONATING + """
            class Net:
                def run(self, x):
                    out = step(self._state, x)
                    return self._state
        """)
        assert _rules_of(fs) == ["donation-use-after-consume"]

    def test_positive_use_on_unreassigned_branch(self, tmp_path):
        # the else path reaches the read with the buffer consumed:
        # "any non-reassigned path" is the contract
        fs = _scan_snippet(tmp_path, self.DONATING + """
            def run(state, x, cond):
                out = step(state, x)
                if cond:
                    state = out
                return state
        """)
        assert _rules_of(fs) == ["donation-use-after-consume"]

    def test_negative_reassigned_from_result(self, tmp_path):
        fs = _scan_snippet(tmp_path, self.DONATING + """
            def run(state, x):
                state = step(state, x)
                return state

            def run_loop(state, xs):
                for x in xs:
                    state = step(state, x)
                return state
        """)
        assert fs == []

    def test_negative_killed_on_all_paths(self, tmp_path):
        fs = _scan_snippet(tmp_path, self.DONATING + """
            def run(state, x, cond):
                out = step(state, x)
                if cond:
                    state = out
                else:
                    state = out * 2
                return state
        """)
        assert fs == []

    def test_positive_loop_redispatch_without_rebind(self, tmp_path):
        fs = _scan_snippet(tmp_path, self.DONATING + """
            def run(state, xs):
                for x in xs:
                    out = step(state, x)
                return out
        """)
        assert _rules_of(fs) == ["donation-use-after-consume"]
        assert "next loop iteration" in fs[0].message

    def test_positive_retry_shape_pr10_regression(self, tmp_path):
        """The minimized PR 10 decode_retry bug: a donate_state=True
        dispatch inside the retried callable — a retried attempt re-runs
        against consumed buffers. The fix shape (engine._donate) is
        donation OFF whenever a retry policy is configured."""
        fs = _scan_snippet(tmp_path, """
            import jax
            from mylib.retry import retry_call

            class Engine:
                def _dispatch_step(self, toks):
                    def once():
                        return self.net.rnn_time_step(
                            toks, donate_state=True)
                    return retry_call(once, policy=self._decode_retry)
        """)
        assert "donation-use-after-consume" in _rules_of(fs)
        f = [x for x in fs if x.rule == "donation-use-after-consume"][0]
        assert "retried" in f.message and "decode_retry" in f.message
        assert f.chain  # callee chain rides into --json

    def test_positive_retry_shape_donate_argnums_lambda(self, tmp_path):
        fs = _scan_snippet(tmp_path, self.DONATING + """
            def run(state, x, retry_call, policy):
                return retry_call(lambda: step(state, x), policy)
        """)
        assert "donation-use-after-consume" in _rules_of(fs)

    def test_negative_retry_without_donation(self, tmp_path):
        """The FIXED engine shape: donation resolved off when a retry
        policy exists (donate_state is a non-literal expression), so
        the retried callable consumes nothing."""
        fs = _scan_snippet(tmp_path, """
            import jax
            from mylib.retry import retry_call

            class Engine:
                def _dispatch_step(self, toks):
                    def once():
                        return self.net.rnn_time_step(
                            toks, donate_state=self._donate)
                    return retry_call(once, policy=self._decode_retry)
        """)
        assert fs == []

    def test_cross_module_donating_jit(self, tmp_path):
        """Import-alias resolution: the donating jit lives in another
        module (the serving/paging.py scatter_pages shape)."""
        fs = _scan_project(tmp_path, {
            "paging.py": """
                import jax
                from functools import partial

                @partial(jax.jit, donate_argnums=(0,))
                def scatter(pool, dense):
                    return pool + dense
            """,
            "engine.py": """
                import jax
                from paging import scatter

                def commit(pool, dense):
                    out = scatter(pool, dense)
                    return pool
            """,
        })
        assert _rules_of(fs) == ["donation-use-after-consume"]

    def test_negative_same_named_nested_def_not_donating(self, tmp_path):
        """A plain nested ``def step`` in one function must not inherit
        donation from an unrelated function's donating nested ``step``
        (function-local scoping of the donation map)."""
        fs = _scan_snippet(tmp_path, """
            import jax
            from functools import partial

            def builder():
                @partial(jax.jit, donate_argnums=(0,))
                def step(state, x):
                    return state + x
                return step

            def other(state, xs):
                def step(s, x):
                    return s
                out = step(state, xs)
                return state
        """)
        assert fs == []

    def test_positive_nested_donating_def_in_own_scope(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax
            from functools import partial

            def run(state, x):
                @partial(jax.jit, donate_argnums=(0,))
                def step(s, v):
                    return s + v
                out = step(state, x)
                return state
        """)
        assert _rules_of(fs) == ["donation-use-after-consume"]

    def test_negative_try_except_rebuild_kills(self, tmp_path):
        """A reassignment inside try whose handler cannot fall through
        (bare raise) kills on every continuing path — the repo's
        recovery-path shape."""
        fs = _scan_snippet(tmp_path, self.DONATING + """
            def run(state, x, rebuild):
                out = step(state, x)
                try:
                    state = rebuild(out)
                except Exception:
                    raise
                return state
        """)
        assert fs == []

    def test_positive_try_handler_falls_through_unkilled(self, tmp_path):
        fs = _scan_snippet(tmp_path, self.DONATING + """
            def run(state, x, rebuild, log):
                out = step(state, x)
                try:
                    state = rebuild(out)
                except Exception:
                    log("rebuild failed")
                return state
        """)
        assert _rules_of(fs) == ["donation-use-after-consume"]

    def test_negative_same_named_plain_method_not_donating(self,
                                                           tmp_path):
        """A plain B.step must not inherit donation from an unrelated
        donating A.step through a bare-name collision (class members
        are keyed Class.name only)."""
        fs = _scan_project(tmp_path, {
            "lib.py": """
                import jax
                from functools import partial

                class A:
                    @partial(jax.jit, donate_argnums=(0,))
                    def step(state, x):
                        return state + x

                class B:
                    def step(self, b, state):
                        return b
            """,
            "use.py": """
                import jax
                from lib import B

                def run(b, state):
                    out = B.step(b, state)
                    return b
            """,
        })
        assert fs == []

    def test_negative_module_assigned_wrapper_refresh(self, tmp_path):
        """``g = jax.jit(f, donate_argnums=...)`` binding form + the
        refresh idiom stays clean."""
        fs = _scan_snippet(tmp_path, """
            import jax

            def _upd(opt, grads):
                return opt

            fast_upd = jax.jit(_upd, donate_argnums=(0,))

            def run(opt, grads):
                opt = fast_upd(opt, grads)
                return opt
        """)
        assert fs == []


# ---------------------------------------------------------------------
# ProjectInfo / CallGraph (ISSUE 13 tentpole plumbing)
# ---------------------------------------------------------------------
class TestProjectInfo:
    def _build(self, tmp_path, files):
        from deeplearning4j_tpu.analysis.project import ProjectInfo
        for name, src in files.items():
            p = tmp_path / name
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
        return ProjectInfo.build([str(tmp_path)], root=str(tmp_path))

    def test_module_naming_and_packages(self, tmp_path):
        proj = self._build(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/sub/__init__.py": "",
            "pkg/sub/mod.py": "def f():\n    return 1\n",
            "top.py": "X = 1\n",
        })
        assert set(proj.modules) == {"pkg", "pkg.sub", "pkg.sub.mod",
                                     "top"}
        assert proj.resolve_name("pkg.sub.mod.f") == ("pkg.sub.mod", "f")

    def test_cross_module_alias_resolution(self, tmp_path):
        proj = self._build(tmp_path, {
            "b.py": "def helper(x):\n    return x\n",
            "a.py": "import b as bee\n\ndef g(x):\n"
                    "    return bee.helper(x)\n",
        })
        mod = proj.modules["a"]
        import ast as _ast
        call = next(n for n in _ast.walk(mod.tree)
                    if isinstance(n, _ast.Call))
        assert proj.resolve_call(mod, call) == ("b", "helper")

    def test_reexport_chain_resolution(self, tmp_path):
        proj = self._build(tmp_path, {
            "b.py": "def helper(x):\n    return x\n",
            "c.py": "from b import helper\n",
            "a.py": "from c import helper\n\ndef g(x):\n"
                    "    return helper(x)\n",
        })
        assert proj.resolve_name("c.helper") == ("b", "helper")
        mod = proj.modules["a"]
        import ast as _ast
        call = next(n for n in _ast.walk(mod.tree)
                    if isinstance(n, _ast.Call))
        assert proj.resolve_call(mod, call) == ("b", "helper")

    def test_reexport_cycle_is_bounded(self, tmp_path):
        proj = self._build(tmp_path, {
            "a.py": "from b import thing\n",
            "b.py": "from a import thing\n",
        })
        assert proj.resolve_name("a.thing") is None  # no hang, no def

    def test_import_graph(self, tmp_path):
        proj = self._build(tmp_path, {
            "a.py": "import b\nimport os\n",
            "b.py": "import c\n",
            "c.py": "",
        })
        g = proj.import_graph()
        assert g["a"] == {"b"} and g["b"] == {"c"} and g["c"] == set()


class TestCallGraph:
    def _graph(self, tmp_path, files):
        from deeplearning4j_tpu.analysis.project import ProjectInfo
        for name, src in files.items():
            (tmp_path / name).write_text(textwrap.dedent(src))
        proj = ProjectInfo.build([str(tmp_path)], root=str(tmp_path))
        return proj.callgraph

    def test_direct_effect_summary(self, tmp_path):
        cg = self._graph(tmp_path, {"m.py": """
            import jax

            def helper(x):
                return jax.device_get(x)
        """})
        ev = cg.reaches("m:helper", frozenset({"host_sync"}))
        assert ev is not None
        effect, chain = ev
        assert effect.what == "jax.device_get()" and chain == ("m:helper",)

    def test_bounded_depth_cutoff(self, tmp_path):
        src = """
            import jax

            def h1(x):
                return h2(x)

            def h2(x):
                return h3(x)

            def h3(x):
                return h4(x)

            def h4(x):
                return jax.device_get(x)
        """
        cg = self._graph(tmp_path, {"m.py": src})
        # h2 -> h3 -> h4: three hops, within the bound
        assert cg.reaches("m:h2", frozenset({"host_sync"})) is not None
        # h1 -> h2 -> h3 -> h4: four hops, beyond MAX_DEPTH=3
        assert cg.reaches("m:h1", frozenset({"host_sync"})) is None

    def test_cycle_between_modules_terminates(self, tmp_path):
        cg = self._graph(tmp_path, {
            "a.py": """
                import jax
                import b

                def fa(x):
                    return b.fb(x)
            """,
            "b.py": """
                import jax
                import a

                def fb(x):
                    a.fa(x)
                    return jax.device_get(x)
            """,
        })
        ev = cg.reaches("a:fa", frozenset({"host_sync"}))
        assert ev is not None and ev[1] == ("a:fa", "b:fb")

    def test_callee_suppression_kills_propagation(self, tmp_path):
        cg = self._graph(tmp_path, {"m.py": """
            import jax

            def helper(x):
                # contract: the ONE sanctioned end-of-fit barrier
                # tpulint: disable=host-sync-in-hot-loop
                return jax.device_get(x)
        """})
        assert cg.reaches("m:helper", frozenset({"host_sync"})) is None

    def test_memo_guarded_transfer_not_an_effect(self, tmp_path):
        """The cached-table idiom: a transfer behind an ``is None``
        memo guard runs once per invalidation, not per call."""
        cg = self._graph(tmp_path, {"m.py": """
            import jax.numpy as jnp

            class E:
                def tables(self):
                    if self._cache is None:
                        self._cache = jnp.asarray(self._np())
                    return self._cache

                def fresh(self):
                    return jnp.asarray(self._np())
        """})
        assert cg.reaches("m:E.tables",
                          frozenset({"device_transfer"})) is None
        assert cg.reaches("m:E.fresh",
                          frozenset({"device_transfer"})) is not None


# ---------------------------------------------------------------------
# interprocedural promotion of the hot-loop rules (ISSUE 13 tentpole)
# ---------------------------------------------------------------------
class TestInterproceduralHostSync:
    def test_helper_sync_flagged_at_call_site_with_chain(self, tmp_path):
        fs = _scan_project(tmp_path, {
            "util.py": """
                import jax

                def materialize(x):
                    return jax.device_get(x)
            """,
            "net.py": """
                import jax
                from util import materialize

                def fit(model, batches):
                    for b in batches:
                        loss = model.step(b)
                        materialize(loss)
            """,
        })
        assert _rules_of(fs) == ["host-sync-in-hot-loop"]
        f = fs[0]
        assert f.path == "net.py" and "materialize" in f.message
        assert f.chain and "util.py" in f.chain[-1]

    def test_two_hop_chain_through_self_method(self, tmp_path):
        fs = _scan_project(tmp_path, {
            "net.py": """
                import jax

                class Net:
                    def _materialize(self, x):
                        return jax.device_get(x)

                    def _publish(self, x):
                        return self._materialize(x)

                    def _fit_batch(self, ds):
                        loss = self.step(ds)
                        self._publish(loss)
            """,
        })
        assert _rules_of(fs) == ["host-sync-in-hot-loop"]
        assert "Net._publish" in fs[0].message \
            and "Net._materialize" in fs[0].message

    def test_negative_clean_helper_and_cold_call_site(self, tmp_path):
        fs = _scan_project(tmp_path, {
            "util.py": """
                import jax

                def shapes(x):
                    return x.shape

                def materialize(x):
                    return jax.device_get(x)
            """,
            "net.py": """
                import jax
                from util import materialize, shapes

                def fit(model, batches):
                    for b in batches:
                        shapes(b)          # clean helper: no finding
                    return materialize(model.params)  # after the loop
            """,
        })
        assert fs == []

    def test_negative_hot_named_callee_not_doubled(self, tmp_path):
        """A helper that is itself hot-named gets its own body finding;
        the call site must not add a second one."""
        fs = _scan_project(tmp_path, {
            "net.py": """
                import jax

                class Net:
                    def _fit_batch(self, ds):
                        return float(self.step(ds))

                    def fit(self, batches):
                        for b in batches:
                            self._fit_batch(b)
            """,
        })
        assert _rules_of(fs) == ["host-sync-in-hot-loop"]
        assert fs[0].line != 0 and "float()" in fs[0].message

    def test_callee_suppression_covers_every_caller(self, tmp_path):
        fs = _scan_project(tmp_path, {
            "util.py": """
                import jax

                def cadence_flush(x):
                    # sanctioned: runs every N batches by contract
                    # tpulint: disable=host-sync-in-hot-loop
                    return jax.device_get(x)
            """,
            "net.py": """
                import jax
                from util import cadence_flush

                def fit(model, batches):
                    for b in batches:
                        cadence_flush(model.score)
            """,
        })
        assert fs == []


class TestInterproceduralDeviceTransfer:
    def test_helper_transfer_flagged_at_call_site(self, tmp_path):
        fs = _scan_project(tmp_path, {
            "stage.py": """
                import jax
                import jax.numpy as jnp

                def to_device(x):
                    return jnp.asarray(x)
            """,
            "net.py": """
                import jax
                from stage import to_device

                class Net:
                    def _fit_batch(self, ds):
                        x = to_device(ds.features)
                        return self.step(x)
            """,
        })
        assert _rules_of(fs) == ["device-transfer-in-hot-loop"]
        assert "to_device" in fs[0].message and fs[0].chain

    def test_negative_memo_guarded_cache_helper(self, tmp_path):
        """The engine's cached-table shape: the helper's transfer sits
        behind an is-None memo guard — steady-state calls are free."""
        fs = _scan_project(tmp_path, {
            "net.py": """
                import jax
                import jax.numpy as jnp

                class Engine:
                    def _tables_dev(self):
                        if self._cache is None:
                            self._cache = jnp.asarray(self._np())
                        return self._cache

                    def _dispatch_step(self):
                        return self._decode(self._tables_dev())
            """,
        })
        assert fs == []


# ---------------------------------------------------------------------
# rule: dtype-promotion
# ---------------------------------------------------------------------
class TestDtypePromotionRule:
    def test_positive_np_float64_in_jax_module(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax.numpy as jnp
            import numpy as np

            def prep(x):
                return jnp.asarray(np.asarray(x, np.float64))
        """)
        assert _rules_of(fs) == ["dtype-promotion"]

    def test_positive_enable_x64_outside_shim(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            jax.config.update("jax_enable_x64", True)
        """)
        assert _rules_of(fs) == ["dtype-promotion"]

    def test_negative_no_jax_import(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import numpy as np

            def stats(x):
                return np.asarray(x, np.float64).mean()
        """)
        assert fs == []

    def test_negative_gradient_check_module_exempt(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax.numpy as jnp
            import numpy as np

            def check(p):
                return jnp.asarray(p, jnp.float64)
        """, name="gradient_check.py")
        assert fs == []


# ---------------------------------------------------------------------
# rule: int8-promotion-in-dispatch (ISSUE 18)
# ---------------------------------------------------------------------
class TestInt8PromotionRule:
    def test_positive_binop_on_int8_local(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax.numpy as jnp

            def dequant(x, sigma):
                q = x.astype(jnp.int8)
                return q * sigma
        """)
        assert _rules_of(fs) == ["int8-promotion-in-dispatch"]
        assert "'q'" in fs[0].message

    def test_positive_int8_into_dot(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax.numpy as jnp

            def score(q, k_ref):
                kq = jnp.asarray(k_ref, dtype=jnp.int8)
                return jnp.dot(q, kq)
        """)
        assert _rules_of(fs) == ["int8-promotion-in-dispatch"]
        assert "dot" in fs[0].message

    def test_negative_explicit_widen_before_math(self, tmp_path):
        """The quant-kernel contract shape: every int8 read widens
        through .astype before touching arithmetic."""
        fs = _scan_snippet(tmp_path, """
            import jax.numpy as jnp

            def dequant(x, sigma):
                q = x.astype(jnp.int8)
                return q.astype(jnp.float32) * sigma
        """)
        assert fs == []

    def test_negative_rebinding_clears_the_taint(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax.numpy as jnp

            def roundtrip(x, sigma):
                q = x.astype(jnp.int8)
                q = q.astype(jnp.float32)
                return q * sigma
        """)
        assert fs == []

    def test_negative_no_jax_import(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import numpy as np

            def pack(x):
                q = x.astype(np.int8)
                return q * 2
        """)
        assert fs == []


# ---------------------------------------------------------------------
# rule: unlocked-thread-state
# ---------------------------------------------------------------------
class TestThreadSharedStateRule:
    def test_positive_unlocked_self_mutation_in_target(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import threading

            class Server:
                def start(self):
                    self._t = threading.Thread(target=self._loop)
                    self._t.start()

                def _loop(self):
                    self.count = 0
                    while True:
                        self.count += 1
        """)
        assert _rules_of(fs) == ["unlocked-thread-state"] * 2

    def test_negative_mutation_under_lock(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import threading

            class Server:
                def start(self):
                    self._lock = threading.Lock()
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    while True:
                        with self._lock:
                            self.count = 1
        """)
        assert fs == []

    def test_negative_queue_handoff_untouched(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import queue
            import threading

            class Server:
                def start(self):
                    self.q = queue.Queue()
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    while True:
                        item = self.q.get()
                        item.event.set()
        """)
        assert fs == []


# ---------------------------------------------------------------------
# rules: hygiene
# ---------------------------------------------------------------------
class TestHygieneRules:
    def test_positive_bare_except_and_mutable_default(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            def load(path, cache={}):
                try:
                    return cache[path]
                except:
                    return None
        """)
        assert _rules_of(fs) == ["bare-except", "mutable-default-arg"]

    def test_negative_typed_except_and_none_default(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            def load(path, cache=None):
                try:
                    return (cache or {})[path]
                except KeyError:
                    return None
        """)
        assert fs == []


# ---------------------------------------------------------------------
# rule: lock-held-across-dispatch
# ---------------------------------------------------------------------
class TestLockHeldAcrossDispatchRule:
    def test_positive_jitted_and_syncs_under_lock(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import threading
            import jax
            from functools import partial

            @jax.jit
            def _dispatch(x):
                return x + 1

            @partial(jax.jit, donate_argnums=(0,))
            def _donate(x):
                return x * 2

            class Engine:
                def step(self, x, scratch):
                    with self._lock:
                        y = _dispatch(x)
                        z = _donate(scratch)  # scratch never reused
                        w = self.net.rnn_time_step(x)
                        jax.device_get(y)
                        y.block_until_ready()
                    return y
        """)
        assert _rules_of(fs) == ["lock-held-across-dispatch"] * 5

    def test_positive_known_dispatch_helpers(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import threading
            from deeplearning4j_tpu.util.decoding import step_tokens
            from deeplearning4j_tpu.serving.paging import gather_pages

            class Engine:
                def step(self, toks):
                    with self._lock:
                        view = gather_pages(self.pools, self.table,
                                            length=8)
                        return step_tokens(self.net, toks, 12)
        """)
        assert _rules_of(fs) == ["lock-held-across-dispatch"] * 2

    def test_negative_snapshot_under_lock_dispatch_outside(self,
                                                           tmp_path):
        fs = _scan_snippet(tmp_path, """
            import threading
            import jax

            @jax.jit
            def _dispatch(x):
                return x + 1

            class Engine:
                def step(self, x):
                    with self._lock:
                        snap = dict(self.state)   # host-only under lock
                    return _dispatch(snap)        # dispatch outside
        """)
        assert fs == []

    def test_negative_condition_wait_is_the_queue_idiom(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import threading
            import jax

            @jax.jit
            def _dispatch(x):
                return x + 1

            class Q:
                def pop(self, x):
                    with self._cond:
                        self._cond.wait(0.1)
                        return _dispatch(x)       # cond, not a lock
        """)
        assert fs == []

    def test_negative_lock_in_outer_function_not_this_scope(self,
                                                            tmp_path):
        fs = _scan_snippet(tmp_path, """
            import threading
            import jax

            @jax.jit
            def _dispatch(x):
                return x + 1

            def outer(self, x):
                with self._lock:
                    def cb():
                        return _dispatch(x)       # runs LATER, unlocked
                    self.cb = cb
        """)
        assert fs == []

    def test_negative_lambda_defined_under_lock_runs_later(self,
                                                           tmp_path):
        fs = _scan_snippet(tmp_path, """
            import threading
            import jax

            @jax.jit
            def _dispatch(x):
                return x + 1

            def outer(self, x):
                with self._lock:
                    self.cb = lambda: _dispatch(x)  # deferred, unlocked
        """)
        assert fs == []

    def test_inline_suppression(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import threading
            import jax

            @jax.jit
            def _dispatch(x):
                return x + 1

            class Engine:
                def step(self, x):
                    with self._lock:
                        # single-threaded dispatcher: submit/health
                        # read lock-free, so only step() waits here
                        # tpulint: disable=lock-held-across-dispatch
                        return _dispatch(x)
        """)
        assert fs == []

    def test_repo_serving_parallel_hot_paths_are_clean(self):
        """The serving engine keeps submit/health/metrics OFF its step
        lock and its dispatches behind method seams that snapshot
        first; the repo carries no lexical lock-held dispatch (any
        future justified hold must carry an inline suppression)."""
        from deeplearning4j_tpu.analysis.rules.lock_dispatch import (
            LockHeldAcrossDispatchRule)
        fs = scan_paths([str(PKG / "serving"), str(PKG / "parallel"),
                         str(PKG / "nn"), str(PKG / "pipeline")],
                        [LockHeldAcrossDispatchRule()], root=str(REPO))
        assert fs == []


# ---------------------------------------------------------------------
# rule: unbounded-retry
# ---------------------------------------------------------------------
class TestUnboundedRetryRule:
    def test_positive_while_true_sleep_swallowing_except(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import time

            def poll(fetch):
                while True:
                    try:
                        return fetch()
                    except ConnectionError:
                        time.sleep(1.0)
        """)
        assert _rules_of(fs) == ["unbounded-retry"]

    def test_positive_sleep_outside_handler_still_counts(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import time

            def wait_for(ready):
                while True:
                    try:
                        if ready():
                            return
                    except OSError:
                        pass
                    time.sleep(0.5)
        """)
        assert _rules_of(fs) == ["unbounded-retry"]

    def test_negative_bounded_attempts_via_raise(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import time

            def fetch_with_cap(fetch, limit=5):
                attempts = 0
                while True:
                    try:
                        return fetch()
                    except ConnectionError:
                        attempts += 1
                        if attempts >= limit:
                            raise
                        time.sleep(0.1 * attempts)
        """)
        assert fs == []

    def test_negative_for_range_and_condition_loops(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import time

            def bounded(fetch):
                for attempt in range(5):
                    try:
                        return fetch()
                    except ConnectionError:
                        time.sleep(0.1)

            def stoppable(fetch, stop):
                while not stop.is_set():
                    try:
                        return fetch()
                    except ConnectionError:
                        time.sleep(0.1)
        """)
        assert fs == []

    def test_positive_nested_escape_does_not_bound(self, tmp_path):
        # the break exits only the inner for, the return lives in a
        # nested def, and the raise is swallowed by an inner try: none
        # of them bounds the retry — still unbounded
        fs = _scan_snippet(tmp_path, """
            import time

            def poll(fetch, alts, probe):
                while True:
                    try:
                        fetch()
                    except OSError:
                        for alt in alts:
                            probe(alt)
                            break
                        def cb():
                            return None
                        try:
                            raise ValueError("inner")
                        except ValueError:
                            pass
                        time.sleep(1.0)
        """)
        assert _rules_of(fs) == ["unbounded-retry"]

    def test_negative_bounded_inner_retry_in_daemon_loop(self, tmp_path):
        # the handler belongs to the bounded inner for, not the daemon
        # while-True — the retry IS bounded by construction
        fs = _scan_snippet(tmp_path, """
            import time

            def daemon(poll):
                while True:
                    for attempt in range(3):
                        try:
                            poll()
                            break
                        except OSError:
                            time.sleep(1.0)
        """)
        assert fs == []

    def test_negative_sleep_without_retry_shape(self, tmp_path):
        # a poll loop that never swallows exceptions is pacing, not retry
        fs = _scan_snippet(tmp_path, """
            import time

            def heartbeat(send):
                while True:
                    send()
                    time.sleep(30.0)
        """)
        assert fs == []

    def test_repo_retry_helper_is_clean(self):
        """The sanctioned helper itself (bounded for-loop) must not trip
        its own rule."""
        from deeplearning4j_tpu.analysis.rules.retry_loop import (
            UnboundedRetryRule)
        fs = scan_paths([str(PKG / "resilience" / "retry.py")],
                        [UnboundedRetryRule()], root=str(REPO))
        assert fs == []


# ---------------------------------------------------------------------
# rule: non-atomic-state-write
# ---------------------------------------------------------------------
class TestNonAtomicStateWriteRule:
    def test_positive_json_dump_onto_final_path(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import json

            def save(path, state):
                with open(path, "w") as f:
                    json.dump(state, f)
        """)
        assert _rules_of(fs) == ["non-atomic-state-write"]

    def test_positive_pickle_dump_wb(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import pickle

            def save(path, model):
                with open(path, "wb") as fh:
                    pickle.dump(model, fh)
        """)
        assert _rules_of(fs) == ["non-atomic-state-write"]

    def test_positive_write_json_dumps(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import json

            def save(path, header, rows):
                with open(path, "w") as f:
                    f.write(json.dumps(header) + "\\n")
                    for r in rows:
                        f.write(r + "\\n")
        """)
        assert _rules_of(fs) == ["non-atomic-state-write"]

    def test_positive_zipfile_model_save(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import zipfile

            def save(path, blob):
                with zipfile.ZipFile(path, "w") as zf:
                    zf.writestr("model.bin", blob)
        """)
        assert _rules_of(fs) == ["non-atomic-state-write"]

    def test_negative_tmp_rename_idiom(self, tmp_path):
        # the sanctioned shape: dump to a tmp path, os.replace into place
        fs = _scan_snippet(tmp_path, """
            import json
            import os

            def save(path, state):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(state, f)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
        """)
        assert fs == []

    def test_negative_append_sink_and_reads(self, tmp_path):
        # append-mode sinks are logs (JSONL exporters), not replace-
        # writes; reads and report-text writes are out of scope
        fs = _scan_snippet(tmp_path, """
            import json

            def log(path, rec):
                with open(path, "a") as f:
                    f.write(json.dumps(rec) + "\\n")

            def load(path):
                with open(path) as f:
                    return json.load(f)

            def report(path, html):
                with open(path, "w") as f:
                    f.write(html)
        """)
        assert fs == []

    def test_repo_atomic_helper_is_exempt(self):
        from deeplearning4j_tpu.analysis.rules.state_write import (
            NonAtomicStateWriteRule)
        fs = scan_paths([str(PKG / "resilience" / "durable.py")],
                        [NonAtomicStateWriteRule()], root=str(REPO))
        assert fs == []

    def test_repo_state_writers_are_clean(self):
        """The satellite fix set: every state writer the rule flagged
        when it landed now goes through the tmp-rename idiom."""
        from deeplearning4j_tpu.analysis.rules.state_write import (
            NonAtomicStateWriteRule)
        targets = ["util/checkpoint.py", "util/model_serializer.py",
                   "nlp/serializer.py", "nlp/pos_tagger.py",
                   "graph/deepwalk.py", "modelimport/dl4j.py",
                   "analysis/baseline.py", "eval/serde.py",
                   "eval/tools.py", "ui/storage.py"]
        fs = scan_paths([str(PKG / t) for t in targets],
                        [NonAtomicStateWriteRule()], root=str(REPO))
        assert fs == []


# ---------------------------------------------------------------------
# rule: stale-world-snapshot
# ---------------------------------------------------------------------
class TestWorldSnapshotRule:
    def test_positive_module_scope_snapshot(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            WORLD = jax.process_count()
            MY_RANK = jax.process_index()
        """)
        assert _rules_of(fs) == ["stale-world-snapshot"] * 2

    def test_positive_class_scope_and_aliased(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            from jax import device_count

            class Trainer:
                n_devices = device_count()
        """)
        assert _rules_of(fs) == ["stale-world-snapshot"]

    def test_positive_argument_default(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            def shard(batch, world=jax.process_count()):
                return batch // world
        """)
        assert _rules_of(fs) == ["stale-world-snapshot"]

    def test_positive_lambda_default_is_definition_time(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            pick = lambda xs, w=jax.process_count(): xs[:w]
        """)
        assert _rules_of(fs) == ["stale-world-snapshot"]

    def test_positive_distributed_wrapper_snapshot(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            from deeplearning4j_tpu.parallel import distributed as dist

            RANK = dist.process_index()
        """)
        assert _rules_of(fs) == ["stale-world-snapshot"]

    def test_negative_call_time_reads(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            def shard(batch):
                return batch // jax.process_count()

            class Trainer:
                def world(self):
                    return jax.process_count()

            pick = lambda xs: xs[jax.process_index()]
        """)
        assert fs == []

    def test_negative_nested_def_default_is_call_time(self, tmp_path):
        # the inner def's defaults evaluate when the OUTER runs — a
        # per-call event, not an import-time snapshot
        fs = _scan_snippet(tmp_path, """
            import jax

            def make_sharder():
                def shard(b, world=jax.process_count()):
                    return b // world
                return shard
        """)
        assert fs == []

    def test_negative_unrelated_module_scope_calls(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import os

            N = os.cpu_count()

            def device_count():
                return 1

            M = device_count()
        """)
        assert fs == []

    def test_repo_world_reads_are_call_time(self):
        """Repo self-scan for this rule specifically: every world read
        in the runtime-facing modules happens at call time (the elastic
        re-mesh contract)."""
        from deeplearning4j_tpu.analysis.rules.world_snapshot import (
            WorldSnapshotRule)
        fs = scan_paths([str(PKG)], [WorldSnapshotRule()], root=str(REPO))
        assert fs == []


# ---------------------------------------------------------------------
# rule: replica-local-state-in-router (ISSUE 14)
# ---------------------------------------------------------------------
class TestReplicaStateRule:
    def _scan_fleet(self, tmp_path, source,
                    name="serving/fleet/router.py"):
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(source))
        return scan_file(str(p), ALL_RULES, root=str(tmp_path))

    def test_positive_engine_internals_in_router(self, tmp_path):
        fs = self._scan_fleet(tmp_path, """
            def score(rep):
                load = len(rep.engine._slots)
                depth = rep.engine._pending.depth()
                return load + depth
        """)
        assert _rules_of(fs) == ["replica-local-state-in-router"] * 2

    def test_positive_seating_and_pool_probes(self, tmp_path):
        fs = self._scan_fleet(tmp_path, """
            def dead_requests(engine):
                out = []
                if engine._seating is not None:
                    out.append(engine._seating)
                return out, engine.page_pool._free
        """, name="serving/fleet/migration.py")
        assert _rules_of(fs) == ["replica-local-state-in-router"] * 3

    def test_negative_public_accessors(self, tmp_path):
        fs = self._scan_fleet(tmp_path, """
            def score(rep, cfg):
                h = rep.engine.health()
                snap = rep.engine.queue_snapshot()
                load = (snap.depth + h["active_slots"]) / h["slots"]
                return load if rep.engine.is_ready() else 1e9

            def migrate(src, dst):
                entries = src.engine.detach_ledger()
                return dst.engine.admit_from_ledger(entries)
        """)
        assert fs == []

    def test_negative_own_private_state_via_self(self, tmp_path):
        fs = self._scan_fleet(tmp_path, """
            class Router:
                def __init__(self):
                    self._replicas = {}
                    self._affinity = {}

                def drop(self, rid):
                    self._replicas.pop(rid, None)
        """)
        assert fs == []

    def test_negative_outside_fleet_modules(self, tmp_path):
        """The engine's OWN modules (and everything else) may touch
        their internals — the rule scopes to serving/fleet/ only."""
        fs = self._scan_fleet(tmp_path, """
            def rebuild(engine):
                return [r for r in engine._slots if r is not None]
        """, name="serving/engine_helper.py")
        assert "replica-local-state-in-router" not in _rules_of(fs)

    def test_inline_suppression(self, tmp_path):
        fs = self._scan_fleet(tmp_path, """
            def peek(engine):
                # test-only chaos seam, justified
                return engine._slots  # tpulint: disable=replica-local-state-in-router
        """)
        assert _rules_of(fs) == []

    def test_repo_fleet_layer_is_clean(self):
        """The shipped fleet layer holds to its own contract: no
        foreign private reads — placement, migration, and autoscaling
        go through public engine accessors only."""
        from deeplearning4j_tpu.analysis.rules.replica_state import (
            ReplicaLocalStateInRouterRule)
        fs = scan_paths([str(PKG / "serving" / "fleet")],
                        [ReplicaLocalStateInRouterRule()], root=str(REPO))
        assert fs == []


# ---------------------------------------------------------------------
# rule: wall-clock-in-traced-body (ISSUE 15)
# ---------------------------------------------------------------------
class TestWallClockRule:
    def test_positive_clock_in_jit_staged_body(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import time
            import jax

            @jax.jit
            def step(x):
                t0 = time.time()          # frozen at trace time
                return x + t0
        """)
        assert "wall-clock-in-traced-body" in _rules_of(fs)

    def test_positive_clock_in_wrapped_function(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import time
            import jax

            def raw(x):
                return x * time.perf_counter()

            fast = jax.jit(raw)
        """)
        assert "wall-clock-in-traced-body" in _rules_of(fs)

    def test_positive_clock_in_step_builder_body(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import time
            import jax

            def _get_train_step(self):
                started = time.monotonic()   # per-build constant

                @jax.jit
                def step(p, batch):
                    return p, started
                return step
        """)
        # one in the builder body; the staged closure reads a captured
        # name, not the clock, so exactly one finding
        assert _rules_of(fs).count("wall-clock-in-traced-body") == 1

    def test_positive_aliased_import(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            from time import perf_counter as clock
            import jax

            def resolve_plan(net):
                jax.jit(lambda x: x)
                return clock()
        """)
        assert "wall-clock-in-traced-body" in _rules_of(fs)

    def test_negative_measure_around_the_dispatch(self, tmp_path):
        """The sanctioned idiom: clock reads AROUND a jitted call, in
        plain host code — never flagged."""
        fs = _scan_snippet(tmp_path, """
            import time

            def _run_dispatch(self, fn):
                t0 = time.perf_counter()
                out = fn()
                self._hist.observe(time.perf_counter() - t0)
                return out

            def step(self):
                now = time.monotonic()
                self._reap(now)
        """)
        assert "wall-clock-in-traced-body" not in _rules_of(fs)

    def test_negative_nested_runtime_thunk_is_host_code(self, tmp_path):
        """A nested def that is neither staged nor jit-building (a
        retry thunk) runs at call time — the innermost scope decides."""
        fs = _scan_snippet(tmp_path, """
            import time
            import jax

            def _get_retry_step(self):
                step = jax.jit(self._raw)

                def once():
                    t0 = time.monotonic()
                    out = step(t0)
                    return out, time.monotonic() - t0
                return once
        """)
        assert "wall-clock-in-traced-body" not in _rules_of(fs)

    def test_negative_module_scope_read(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import time
            import jax

            _T0 = time.time()   # import-time host constant, explicit

            @jax.jit
            def step(x):
                return x
        """)
        assert "wall-clock-in-traced-body" not in _rules_of(fs)

    def test_inline_suppression(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import time
            import jax

            @jax.jit
            def step(x):
                # build stamp, deliberately frozen
                t0 = time.time()  # tpulint: disable=wall-clock-in-traced-body
                return x + t0
        """)
        assert "wall-clock-in-traced-body" not in _rules_of(fs)

    def test_repo_self_scan_clean(self):
        """The instrumented serving/resilience/monitoring hot paths
        read clocks only in host code — the shipped tree carries zero
        findings (and zero baseline entries) for this rule."""
        from deeplearning4j_tpu.analysis.rules.wall_clock import (
            WallClockInTracedBodyRule)
        fs = scan_paths([str(PKG)], [WallClockInTracedBodyRule()],
                        root=str(REPO))
        assert fs == []


# ---------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------
class TestSuppression:
    SRC = """
        import jax

        def _fit_batch(self, ds):
            loss = self.step(ds)
            self.score = float(loss)  # tpulint: disable=host-sync-in-hot-loop
            # justified: final-batch barrier
            # tpulint: disable=host-sync-in-hot-loop
            jax.block_until_ready(self.params)
    """

    def test_inline_and_next_line_suppressions(self, tmp_path):
        assert _scan_snippet(tmp_path, self.SRC) == []

    def test_unsuppressed_sibling_still_fires(self, tmp_path):
        fs = _scan_snippet(tmp_path, self.SRC + """
            def _fit_other(self, ds):
                return float(self.step(ds))
        """)
        assert _rules_of(fs) == ["host-sync-in-hot-loop"]

    def test_disable_all_wildcard(self, tmp_path):
        fs = _scan_snippet(tmp_path, """
            import jax

            def _fit_batch(self, ds):
                return float(self.step(ds))  # tpulint: disable=all
        """)
        assert fs == []


# ---------------------------------------------------------------------
# baseline round-trip + CLI
# ---------------------------------------------------------------------
BAD_SRC = """
import jax

def _fit_batch(self, ds):
    return float(self.step(ds))
"""


class TestBaselineAndCli:
    def test_baseline_roundtrip(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text(BAD_SRC)
        findings = scan_paths([str(mod)], root=str(tmp_path))
        assert _rules_of(findings) == ["host-sync-in-hot-loop"]

        bpath = tmp_path / bl.BASELINE_NAME
        bl.write_baseline(str(bpath), findings)
        again = scan_paths([str(mod)], root=str(tmp_path))
        new, matched, stale = bl.split_new(again, bl.load_baseline(str(bpath)))
        assert new == [] and matched == 1 and stale == []

        # a NEW violation is not absorbed by the old baseline
        mod.write_text(BAD_SRC + "\n\ndef _fit_more(self, ds):\n"
                       "    return float(self.step(ds))\n")
        third = scan_paths([str(mod)], root=str(tmp_path))
        new, matched, stale = bl.split_new(third, bl.load_baseline(str(bpath)))
        assert matched == 1 and len(new) == 1

    def test_baseline_stale_entries_reported(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text(BAD_SRC)
        findings = scan_paths([str(mod)], root=str(tmp_path))
        bpath = tmp_path / bl.BASELINE_NAME
        bl.write_baseline(str(bpath), findings)
        mod.write_text("import jax\n")  # debt paid off
        new, matched, stale = bl.split_new(
            scan_paths([str(mod)], root=str(tmp_path)),
            bl.load_baseline(str(bpath)))
        assert new == [] and matched == 0 and len(stale) == 1

    def test_cli_json_exit_codes(self, tmp_path, capsys):
        mod = tmp_path / "m.py"
        mod.write_text(BAD_SRC)
        rc = main([str(mod), "--format", "json",
                   "--baseline", str(tmp_path / bl.BASELINE_NAME)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert report["total"] == 1 and len(report["new"]) == 1
        assert report["new"][0]["rule"] == "host-sync-in-hot-loop"

        rc = main([str(mod), "--write-baseline",
                   "--baseline", str(tmp_path / bl.BASELINE_NAME)])
        capsys.readouterr()
        assert rc == 0
        rc = main([str(mod), "--format", "json",
                   "--baseline", str(tmp_path / bl.BASELINE_NAME)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0 and report["new"] == [] and report["baselined"] == 1

    def test_cli_rule_selection_and_errors(self, tmp_path, capsys):
        mod = tmp_path / "m.py"
        mod.write_text("try:\n    pass\nexcept:\n    pass\n")
        rc = main([str(mod), "--no-baseline", "--rules", "bare-except"])
        capsys.readouterr()
        assert rc == 1
        rc = main([str(mod), "--no-baseline", "--rules", "mutable-default-arg"])
        capsys.readouterr()
        assert rc == 0
        assert main([str(mod), "--rules", "no-such-rule"]) == 2
        assert main([str(tmp_path / "missing.py")]) == 2
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ALL_RULES:
            assert rule.id in out

    def test_parse_error_is_a_new_finding(self, tmp_path, capsys):
        mod = tmp_path / "m.py"
        mod.write_text("def broken(:\n")
        rc = main([str(mod), "--format", "json", "--no-baseline"])
        report = json.loads(capsys.readouterr().out)
        assert rc == 1 and report["new"][0]["rule"] == "parse-error"

    def test_single_rule_flag_and_baseline_scope(self, tmp_path, capsys):
        """--rule runs one rule; baseline entries of unselected rules
        are out of scope, not stale."""
        mod = tmp_path / "m.py"
        mod.write_text(BAD_SRC)
        bpath = tmp_path / bl.BASELINE_NAME
        bl.write_baseline(str(bpath),
                          scan_paths([str(mod)], root=str(tmp_path)))
        rc = main([str(mod), "--rule", "bare-except",
                   "--format", "json", "--baseline", str(bpath)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert report["stale_baseline"] == [] and report["total"] == 0
        rc = main([str(mod), "--rule", "host-sync-in-hot-loop",
                   "--format", "json", "--baseline", str(bpath)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0 and report["baselined"] == 1

    def test_stale_baseline_is_a_hard_failure(self, tmp_path, capsys):
        """ISSUE 13 ratchet hardening: paid-off debt must be ratcheted
        out of the baseline, or the lane fails."""
        mod = tmp_path / "m.py"
        mod.write_text(BAD_SRC)
        bpath = tmp_path / bl.BASELINE_NAME
        bl.write_baseline(str(bpath),
                          scan_paths([str(mod)], root=str(tmp_path)))
        mod.write_text("import jax\n")  # debt paid off
        rc = main([str(mod), "--format", "json",
                   "--baseline", str(bpath)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert report["new"] == [] and len(report["stale_baseline"]) == 1

    def test_update_baseline_refuses_error_severity(self, tmp_path,
                                                    capsys):
        """--update-baseline will not silently grandfather an
        error-severity finding; --allow-grandfather is the reviewed
        escape hatch, and warning-severity additions pass freely."""
        mod = tmp_path / "m.py"
        mod.write_text(BAD_SRC)   # host-sync: severity error
        bpath = tmp_path / bl.BASELINE_NAME
        rc = main([str(mod), "--update-baseline",
                   "--baseline", str(bpath)])
        capsys.readouterr()
        assert rc == 1 and not bpath.exists()
        rc = main([str(mod), "--update-baseline", "--allow-grandfather",
                   "--baseline", str(bpath)])
        capsys.readouterr()
        assert rc == 0 and bpath.exists()
        # ratchet down once the debt is paid: stale entry drops
        mod.write_text(BAD_SRC)
        rc = main([str(mod), "--update-baseline",
                   "--baseline", str(bpath)])
        capsys.readouterr()
        assert rc == 0  # unchanged content: nothing newly grandfathered
        # a WARNING-severity addition needs no flag
        mod.write_text(
            "import jax\nimport jax.numpy as jnp\n\n\n"
            "class Net:\n    def _fit_batch(self, ds):\n"
            "        return self.step(jnp.asarray(ds.features))\n")
        rc = main([str(mod), "--update-baseline",
                   "--baseline", str(bpath)])
        out = capsys.readouterr()
        assert rc == 0, out.err
        data = json.loads(bpath.read_text())
        assert all(e["rule"] == "device-transfer-in-hot-loop"
                   for e in data["findings"].values())


# ---------------------------------------------------------------------
# --diff: the O(diff) CI gate (ISSUE 13) against a synthetic repo
# ---------------------------------------------------------------------
import shutil
import subprocess


@pytest.mark.skipif(shutil.which("git") is None, reason="git required")
class TestDiffMode:
    CLEAN = "import jax\n\n\ndef prep(x):\n    return x\n"

    def _git(self, repo, *args):
        subprocess.run(
            ["git", "-C", str(repo), "-c", "user.email=t@t",
             "-c", "user.name=t", *args],
            check=True, capture_output=True)

    def _repo(self, tmp_path):
        """Three clean modules, committed; b.py then gains a violation
        in the working tree (the diff includes uncommitted changes)."""
        repo = tmp_path / "r"
        repo.mkdir()
        for name in ("a.py", "b.py", "c.py"):
            (repo / name).write_text(self.CLEAN)
        self._git(repo, "init", "-q")
        self._git(repo, "add", "-A")
        self._git(repo, "commit", "-qm", "seed")
        (repo / "b.py").write_text(
            self.CLEAN + "\n\ndef _fit_batch(self, ds):\n"
            "    return float(self.step(ds))\n")
        return repo

    def test_diff_scans_only_changed_modules(self, tmp_path, capsys):
        repo = self._repo(tmp_path)
        rc = main([str(repo), "--format", "json", "--diff", "HEAD",
                   "--baseline", str(repo / bl.BASELINE_NAME)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert report["scanned_modules"] == 1
        assert report["total_modules"] == 3
        assert report["diff_base"] == "HEAD"
        assert [f["rule"] for f in report["new"]] == \
            ["host-sync-in-hot-loop"]
        assert report["new"][0]["path"] == "b.py"
        assert report["new"][0]["on_changed_line"] is True

    def test_diff_respects_baseline_without_stale_noise(self, tmp_path,
                                                        capsys):
        """A grandfathered finding in an UNCHANGED module is out of the
        diff's scope (not stale); one in the CHANGED module still
        absorbs its finding."""
        repo = self._repo(tmp_path)
        # plant a violation in c.py too and baseline the full scan
        (repo / "c.py").write_text(
            self.CLEAN + "\n\ndef _fit_other(self, ds):\n"
            "    return float(self.step(ds))\n")
        findings = scan_paths([str(repo)], root=str(repo))
        bpath = repo / bl.BASELINE_NAME
        bl.write_baseline(str(bpath), findings)
        self._git(repo, "add", "-A")
        self._git(repo, "commit", "-qm", "grandfathered")
        # new working-tree violation in b.py only
        (repo / "b.py").write_text(
            (repo / "b.py").read_text() +
            "\n\ndef _fit_more(self, ds):\n"
            "    return self.params.block_until_ready()\n")
        rc = main([str(repo), "--format", "json", "--diff", "HEAD",
                   "--baseline", str(bpath)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert report["scanned_modules"] == 1   # b.py only: O(diff)
        assert report["stale_baseline"] == []   # c.py is out of scope
        assert report["baselined"] == 1         # b.py's old finding
        assert [f["rule"] for f in report["new"]] == \
            ["host-sync-in-hot-loop"]
        # the full scan reproduces the identical grandfathered set:
        # fingerprint-for-fingerprint, plus the same single new finding
        rc = main([str(repo), "--format", "json",
                   "--baseline", str(bpath)])
        full = json.loads(capsys.readouterr().out)
        assert full["scanned_modules"] == 3
        assert full["baselined"] == 2 and full["stale_baseline"] == []
        assert [f["fingerprint"] for f in full["new"]] == \
            [f["fingerprint"] for f in report["new"]]

    def test_diff_refuses_baseline_writes_and_bad_ref(self, tmp_path,
                                                      capsys):
        repo = self._repo(tmp_path)
        bpath = repo / bl.BASELINE_NAME
        assert main([str(repo), "--diff", "HEAD", "--write-baseline",
                     "--baseline", str(bpath)]) == 2
        assert main([str(repo), "--diff", "HEAD", "--update-baseline",
                     "--baseline", str(bpath)]) == 2
        assert main([str(repo), "--diff", "no-such-ref",
                     "--baseline", str(bpath)]) == 2
        capsys.readouterr()

    def test_rule_subset_refuses_baseline_writes(self, tmp_path, capsys):
        """A rule-subset scan must never become the baseline either —
        it would wipe every other rule's grandfathered entries."""
        repo = self._repo(tmp_path)
        bpath = repo / bl.BASELINE_NAME
        assert main([str(repo), "--rule", "bare-except",
                     "--write-baseline", "--baseline", str(bpath)]) == 2
        assert main([str(repo), "--rules", "bare-except",
                     "--update-baseline", "--baseline", str(bpath)]) == 2
        assert not bpath.exists()
        capsys.readouterr()

    def test_diff_with_baseline_below_repo_toplevel(self, tmp_path,
                                                    capsys):
        """git emits toplevel-relative paths; a baseline anchored in a
        subdirectory must not make the diff scan silently empty."""
        repo = self._repo(tmp_path)
        sub = repo / "ci"
        sub.mkdir()
        rc = main([str(repo), "--format", "json", "--diff", "HEAD",
                   "--baseline", str(sub / bl.BASELINE_NAME)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert report["scanned_modules"] == 1
        assert [f["rule"] for f in report["new"]] == \
            ["host-sync-in-hot-loop"]
        assert report["new"][0]["on_changed_line"] is True

    def test_changed_callee_flags_unchanged_caller(self, tmp_path,
                                                   capsys):
        """The impact closure: a changed CALLEE growing an effect
        surfaces its interprocedural finding in an UNCHANGED caller —
        the diff scan must include the reverse-import closure."""
        repo = tmp_path / "r2"
        repo.mkdir()
        (repo / "helper.py").write_text(
            "import jax\n\n\ndef summarize(x):\n    return x\n")
        (repo / "train.py").write_text(
            "import jax\nfrom helper import summarize\n\n\n"
            "def fit(model, batches):\n    for b in batches:\n"
            "        summarize(model.step(b))\n")
        (repo / "leaf.py").write_text(self.CLEAN)
        self._git(repo, "init", "-q")
        self._git(repo, "add", "-A")
        self._git(repo, "commit", "-qm", "seed")
        # the helper grows a sync; train.py is untouched
        (repo / "helper.py").write_text(
            "import jax\n\n\ndef summarize(x):\n"
            "    return jax.device_get(x)\n")
        rc = main([str(repo), "--format", "json", "--diff", "HEAD",
                   "--baseline", str(repo / bl.BASELINE_NAME)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert report["scanned_modules"] == 2   # helper + its importer
        assert [f["path"] for f in report["new"]] == ["train.py"]
        assert report["new"][0]["rule"] == "host-sync-in-hot-loop"
        assert report["new"][0]["chain"]

    def test_untracked_new_module_is_scanned(self, tmp_path, capsys):
        """A brand-new module is invisible to `git diff <base>` until
        added — the gate must still scan it (fully changed)."""
        repo = self._repo(tmp_path)
        (repo / "b.py").write_text(self.CLEAN)  # undo the tracked change
        (repo / "fresh.py").write_text(
            "import jax\n\n\ndef _fit_batch(self, ds):\n"
            "    return float(self.step(ds))\n")
        rc = main([str(repo), "--format", "json", "--diff", "HEAD",
                   "--baseline", str(repo / bl.BASELINE_NAME)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert report["scanned_modules"] == 1
        assert report["new"][0]["path"] == "fresh.py"
        assert report["new"][0]["on_changed_line"] is True


# ---------------------------------------------------------------------
# the gate: repo must scan clean against the committed baseline
# ---------------------------------------------------------------------
class TestSelfScan:
    def test_repo_has_zero_non_baselined_findings(self, capsys):
        rc = main([str(PKG), "--format", "json",
                   "--baseline", str(REPO / bl.BASELINE_NAME)])
        report = json.loads(capsys.readouterr().out)
        assert report["new"] == [], (
            "new tpulint findings (fix them, suppress with justification, "
            "or — for pre-existing debt only — re-baseline):\n" +
            "\n".join(f"{f['path']}:{f['line']} [{f['rule']}] {f['message']}"
                      for f in report["new"]))
        assert rc == 0

    def test_committed_baseline_has_no_stale_entries(self, capsys):
        rc = main([str(PKG), "--format", "json",
                   "--baseline", str(REPO / bl.BASELINE_NAME)])
        report = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert report["stale_baseline"] == [], (
            "baseline entries no longer observed — ratchet down with "
            "--write-baseline")

    def test_every_rule_family_is_registered(self):
        assert {r.id for r in ALL_RULES} == {
            "host-sync-in-hot-loop", "device-transfer-in-hot-loop",
            "tracer-leak", "recompile-hazard",
            "dtype-promotion", "int8-promotion-in-dispatch",
            "unlocked-thread-state", "bare-except",
            "mutable-default-arg", "unbounded-retry",
            "non-atomic-state-write", "stale-world-snapshot",
            "lock-held-across-dispatch",
            "donation-use-after-consume", "jit-key-drift",
            "replica-local-state-in-router",
            "wall-clock-in-traced-body"}
        assert RULES_BY_ID["host-sync-in-hot-loop"].severity == "error"
        assert RULES_BY_ID["device-transfer-in-hot-loop"].severity == \
            "warning"
        assert RULES_BY_ID["donation-use-after-consume"].severity == \
            "error"
        assert RULES_BY_ID["jit-key-drift"].severity == "warning"
