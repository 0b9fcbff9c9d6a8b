"""Bring-up contracts that hold without a chip (ISSUE 21).

- the compile-cache helper takes its directory from outside
  (``JAX_COMPILATION_CACHE_DIR``) or falls back to ONE fixed in-checkout
  path — never a temp / pid / clock path, because the path is part of
  the cache key;
- ``chip_smoke.py`` without the dry-run flag refuses a CPU backend
  (non-zero exit, platform printed, no result line) before compiling
  anything;
- the paged-kernel shape gate and ``decode_impl="auto"`` agree for the
  default ``PagedKVConfig``;
- ``elastic_initialize`` builds its call against the installed jax's
  ``State.initialize`` signature.

The full tiny CPU dry run of ``chip_smoke.py`` is marked ``slow``.
"""

import inspect
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*flags, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)      # one CPU device: multichip "not run"
    return subprocess.run([sys.executable, SMOKE, *flags], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=timeout)


class TestCompileCacheHelper:
    def test_env_dir_is_used_and_nothing_set_in_code(self, monkeypatch,
                                                     tmp_path):
        import jax

        from deeplearning4j_tpu.util import compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_one_fixed_in_checkout_path(self, monkeypatch):
        import jax

        from deeplearning4j_tpu.util import compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            first = compile_cache.configure_compile_cache()
            assert first == compile_cache.configure_compile_cache()
            assert first == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_no_temp_pid_or_clock_feeds_the_path(self):
        """The helper imports nothing that could move the path."""
        import ast

        from deeplearning4j_tpu.util import compile_cache
        tree = ast.parse(inspect.getsource(compile_cache))
        imported = {a.name for n in ast.walk(tree)
                    if isinstance(n, ast.Import) for a in n.names}
        imported |= {n.module for n in ast.walk(tree)
                     if isinstance(n, ast.ImportFrom)}
        assert imported <= {"__future__", "os", "jax"}
        assert "getpid" not in inspect.getsource(compile_cache)


class TestSmokeRefusesCpu:
    def test_default_mode_on_cpu_exits_nonzero_and_names_platform(self):
        r = _run_smoke(timeout=120)
        assert r.returncode != 0
        lines = [json.loads(l) for l in r.stdout.splitlines() if l]
        assert lines and lines[0]["platform"] == "cpu"
        assert lines[0]["phase"] == "device" and not lines[0]["passed"]
        assert not any("ok" in l for l in lines)      # no result line
        assert "'cpu'" in r.stderr

    def test_result_line_has_exactly_the_contract_keys(self, monkeypatch):
        """The driver refuses a last line with any key beyond these."""
        monkeypatch.syspath_prepend(REPO)
        import chip_smoke as smoke
        stamp = {"platform": "tpu", "device_kind": "TPU v5 lite",
                 "device_count": 1, "jax": "0.9.0"}
        assert json.loads(smoke.result_line(True, stamp)) == {
            "ok": True, "device": {"platform": "tpu",
                                   "kind": "TPU v5 lite", "count": 1}}

    def test_kernels_mode_has_no_dry_run(self):
        r = _run_smoke("--kernels", "--dry-run-cpu", timeout=60)
        assert r.returncode != 0 and not r.stdout.strip()


class TestGateAndAutoAgree:
    @staticmethod
    def _engine(monkeypatch, head_dim, paging, max_length=32):
        import jax

        from deeplearning4j_tpu.serving import GenerationEngine
        from deeplearning4j_tpu.zoo import TextGenerationTransformer
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        net = TextGenerationTransformer(
            vocab_size=32, embed_dim=2 * head_dim, n_heads=2, n_layers=1,
            max_length=max_length, positional="rope").init()
        net.conf.dtype = "bfloat16"
        return GenerationEngine(net, 32, slots=2, paging=paging)

    @pytest.mark.parametrize("head_dim", [128, 32])
    def test_default_paging_config_resolves_by_the_gate(self, monkeypatch,
                                                        head_dim):
        """On a TPU backend ``decode_impl="auto"`` lands on the kernel
        exactly when the shape gate admits the engine's pool — for the
        DEFAULT page size (8-row bf16 page blocks compile on v5e)."""
        from deeplearning4j_tpu.serving import PagedKVConfig
        from deeplearning4j_tpu.serving.paged_kernel import (
            paged_attention_supported)
        cfg = PagedKVConfig()
        eng = self._engine(monkeypatch, head_dim, cfg)
        want = paged_attention_supported(
            (eng.page_pool.total_pages, 2, cfg.page_size, head_dim), 1)
        assert want == (head_dim == 128)
        assert (eng._decode_impl == "pallas") == want

    def test_int8_pool_too_big_for_smem_resolves_to_xla(self, monkeypatch):
        """The int8 kernel's scale sidecars live in SMEM, so the gate
        bounds the pool size: ~1k pages is past v5e's 1 MiB."""
        from deeplearning4j_tpu.serving import PagedKVConfig
        small = self._engine(monkeypatch, 128, PagedKVConfig(
            page_size=32, kv_dtype="int8", total_pages=256))
        big = self._engine(monkeypatch, 128, PagedKVConfig(
            page_size=32, kv_dtype="int8", total_pages=1024))
        assert small._decode_impl == "pallas"
        assert big._decode_impl == "xla"


def test_elastic_initialize_binds_to_installed_jax(monkeypatch):
    from jax._src import distributed as jdist

    from deeplearning4j_tpu.parallel import distributed as dist
    real_sig = inspect.signature(jdist.global_state.initialize)
    seen = {}
    monkeypatch.setattr(jdist.global_state, "initialize",
                        lambda **kw: seen.update(kw))
    monkeypatch.setattr(dist, "_cpu_platform", lambda: False)
    monkeypatch.setattr(dist, "_initialized", False)
    dist.elastic_initialize("127.0.0.1:1", 2, 0,
                            initialization_timeout=7.0)
    bound = real_sig.bind(**seen)      # TypeError on a stale keyword
    assert bound.arguments["heartbeat_timeout_seconds"] >= 3600
    assert bound.arguments["initialization_timeout"] == 7


@pytest.mark.slow
def test_dry_run_cpu_passes_but_never_prints_a_chip_pass():
    r = _run_smoke("--dry-run-cpu", timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(l) for l in r.stdout.splitlines() if l]
    phases = {l["phase"]: l for l in lines if "phase" in l}
    for name in ("native", "train", "flash", "serve"):
        assert phases[name]["passed"] is True
        assert phases[name]["platform"] == "cpu"
    assert phases["serve"]["kernel"] == "interpret"
    last = lines[-1]                # the summary; a dry run has no result
    assert last["phase"] == "summary" and last["dry_run"]
    assert last["platform"] == "cpu" and last["claim"] is None
    assert not any("ok" in l for l in lines)
