"""Core layer tests (ref test model: cpp/test/core/*)."""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core import (
    DeviceResources,
    KeyValuePair,
    LogicError,
    Resources,
    deserialize_mdspan,
    deserialize_scalar,
    expects,
    operators as ops,
    serialize_mdspan,
    serialize_scalar,
)
from raft_tpu.core.interruptible import Interruptible, InterruptedException, synchronize
from raft_tpu.core.mdarray import check_matrix, check_vector
from raft_tpu.util import Pow2, ceildiv, round_up_safe


class TestResources:
    def test_lazy_slots(self):
        res = Resources()
        assert res.device is not None
        assert res.mesh is not None

    def test_shallow_copy_shares_objects_not_table(self):
        res = Resources()
        obj = object()
        res.set_resource("x", obj)
        copy = Resources(res)
        assert copy.get_resource("x") is obj  # resource objects shared
        # ...but the slot table is independent: rebinding on the copy (or
        # constructor overrides) never mutates the source handle.
        copy.set_resource("x", "other")
        assert res.get_resource("x") is obj
        override = Resources(res, x="tpu1")
        assert override.get_resource("x") == "tpu1"
        assert res.get_resource("x") is obj

    def test_key_stream_advances(self):
        h = DeviceResources(seed=0)
        k1, k2 = h.next_key(), h.next_key()
        assert not np.array_equal(
            jax.random.key_data(k1), jax.random.key_data(k2)
        )

    def test_comms_missing_raises(self):
        res = Resources()
        with pytest.raises(LogicError):
            res.get_comms()

    def test_subcomm_roundtrip(self):
        res = Resources()
        res.set_subcomm("row", "fake-comm")
        assert res.get_subcomm("row") == "fake-comm"


class TestValidation:
    def test_check_matrix(self):
        x = np.zeros((3, 4), np.float32)
        arr = check_matrix(x, rows=3, cols=4, dtype=jnp.float32)
        assert arr.shape == (3, 4)

    def test_check_matrix_bad_shape(self):
        with pytest.raises(LogicError):
            check_matrix(np.zeros((3, 4), np.float32), rows=5)

    def test_check_vector_bad_rank(self):
        with pytest.raises(LogicError):
            check_vector(np.zeros((3, 4), np.float32))

    def test_expects(self):
        expects(True)
        with pytest.raises(LogicError):
            expects(False, "nope")


class TestSerialize:
    def test_mdspan_roundtrip(self):
        buf = io.BytesIO()
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        serialize_mdspan(buf, a)
        serialize_mdspan(buf, jnp.ones((2, 2), jnp.int32))
        buf.seek(0)
        b = deserialize_mdspan(buf)
        c = deserialize_mdspan(buf)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.ones((2, 2), np.int32), c)

    def test_scalar_roundtrip(self):
        buf = io.BytesIO()
        serialize_scalar(buf, 7, np.int64)
        serialize_scalar(buf, 2.5, np.float32)
        buf.seek(0)
        assert deserialize_scalar(buf, np.int64) == 7
        assert deserialize_scalar(buf, np.float32) == np.float32(2.5)


class TestOperators:
    def test_argmin_op(self):
        a = KeyValuePair(jnp.int32(3), jnp.float32(1.0))
        b = KeyValuePair(jnp.int32(1), jnp.float32(1.0))
        out = ops.argmin_op(a, b)
        assert int(out.key) == 1  # tie → smaller key

    def test_compose(self):
        f = ops.compose_op(ops.sqrt_op, ops.sq_op)
        assert float(f(jnp.float32(3.0))) == pytest.approx(3.0)


class TestInterruptible:
    def test_sync_ok(self):
        x = jnp.ones((4,))
        synchronize(x)

    def test_cancel_raises(self):
        tok = Interruptible.get_token()
        tok.cancel()
        with pytest.raises(InterruptedException):
            tok.interruptible_check()
        tok.interruptible_check()  # flag cleared


class TestUtil:
    def test_ceildiv(self):
        assert ceildiv(10, 3) == 4

    def test_pow2(self):
        p = Pow2(128)
        assert p.round_up(130) == 256
        assert p.round_down(130) == 128
        assert p.is_aligned(256)
        assert round_up_safe(5, 4) == 8

    def test_pow2_rejects_non_pow2(self):
        with pytest.raises(ValueError):
            Pow2(100)


class TestTracing:
    """Profiling convention (ref: NVTX range at every public entry,
    core/nvtx.hpp:48-90 + call sites like ivf_pq_build.cuh:1080)."""

    def test_traced_preserves_semantics(self):
        import jax
        import jax.numpy as jnp
        from raft_tpu.core.nvtx import traced

        @traced
        def f(x):
            return x * 2

        assert f.__name__ == "f"
        assert int(f(jnp.asarray(3))) == 6
        # Also under jit (named_scope path).
        assert int(jax.jit(f)(jnp.asarray(4))) == 8

    def test_range_scope_nesting(self):
        from raft_tpu.core.nvtx import pop_range, push_range, range_scope

        with range_scope("outer"):
            push_range("inner")
            pop_range()

    def test_public_entries_are_traced(self):
        # Spot-check the convention at the VERDICT-named surfaces.
        from raft_tpu.matrix.select_k import select_k
        from raft_tpu.neighbors import ivf_flat, ivf_pq
        from raft_tpu.cluster import kmeans_balanced

        for fn in (select_k, ivf_flat.build, ivf_flat.search, ivf_pq.build,
                   ivf_pq.search, kmeans_balanced.fit):
            assert fn.__wrapped__ is not None, fn


class TestLoggerTrace:
    """logger.trace() convenience for the custom TRACE level (ISSUE 5
    satellite): emits at TRACE, silent one notch above."""

    def _capture(self):
        import sys

        import raft_tpu.core.logger  # noqa: F401  (ensure registered)

        # The core package rebinds the ``logger`` attribute to the Logger
        # instance, shadowing the submodule — fetch the module itself.
        L = sys.modules["raft_tpu.core.logger"]

        lines = []
        sink = L.set_callback(lambda lvl, msg: lines.append((lvl, msg)))
        return L, sink, lines

    def test_emits_at_trace_level(self):
        L, sink, lines = self._capture()
        old = L.logger.level
        try:
            L.set_level(L.TRACE)
            L.logger.trace("batch %s dispatched (%s rows)", 3, 8)
            assert len(lines) == 1
            lvl, msg = lines[0]
            assert lvl == L.TRACE
            assert "batch 3 dispatched (8 rows)" in msg
        finally:
            L.logger.removeHandler(sink)
            L.set_level(old)

    def test_silent_above_trace(self):
        L, sink, lines = self._capture()
        old = L.logger.level
        try:
            L.set_level(L.TRACE + 1)
            L.logger.trace("invisible %s", 1)
            L.set_level(L.DEBUG)
            L.logger.trace("still invisible")
            assert lines == []
        finally:
            L.logger.removeHandler(sink)
            L.set_level(old)

    def test_module_level_alias(self):
        L, sink, lines = self._capture()
        old = L.logger.level
        try:
            L.set_level(L.TRACE)
            L.trace("via module alias")
            assert len(lines) == 1 and lines[0][0] == L.TRACE
        finally:
            L.logger.removeHandler(sink)
            L.set_level(old)


class TestCompilationCacheDir:
    """enable_compilation_cache places the persistent cache where the
    deployment says (JAX_COMPILATION_CACHE_DIR, read by JAX into
    jax_compilation_cache_dir) and otherwise at one fixed path inside
    the checkout; it never picks a directory of its own beyond that."""

    @pytest.fixture
    def clean_cache_config(self):
        from jax.experimental.compilation_cache import compilation_cache

        old_dir = jax.config.jax_compilation_cache_dir
        old_min = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            yield
        finally:
            jax.config.update("jax_compilation_cache_dir", old_dir)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              old_min)
            compilation_cache.reset_cache()

    def test_respects_preconfigured_dir(self, tmp_path, clean_cache_config):
        from raft_tpu.core.compilation_cache import enable_compilation_cache

        app_dir = str(tmp_path / "app_cache")
        jax.config.update("jax_compilation_cache_dir", app_dir)
        effective = enable_compilation_cache()
        assert effective == app_dir
        assert jax.config.jax_compilation_cache_dir == app_dir

    def test_env_var_dir_is_left_alone(self, tmp_path):
        """JAX reads JAX_COMPILATION_CACHE_DIR at import, so the rule is
        checked in a fresh CPU-only interpreter."""
        import os
        import subprocess
        import sys

        env_dir = str(tmp_path / "env_cache")
        code = ("import jax\n"
                "from raft_tpu.core.compilation_cache import "
                "enable_compilation_cache\n"
                "print(enable_compilation_cache())\n"
                "print(jax.config.jax_compilation_cache_dir)\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=env_dir,
                   PYTHONPATH=root + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120,
                             check=True)
        assert out.stdout.split() == [env_dir, env_dir]

    def test_unconfigured_uses_checkout_dir(self, clean_cache_config):
        import os

        from raft_tpu.core.compilation_cache import (CHECKOUT_CACHE_DIR,
                                                     enable_compilation_cache)

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert CHECKOUT_CACHE_DIR == os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compilation_cache() == CHECKOUT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR
        assert os.path.isdir(CHECKOUT_CACHE_DIR)
        # Repeat calls keep the same directory (a moved cache never hits).
        assert enable_compilation_cache() == CHECKOUT_CACHE_DIR
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.2

    def test_serve_warmup_leaves_cache_config_alone(self, clean_cache_config):
        """Serving warmup compiles through whatever cache the process
        has; placing the cache is the entry point's call."""
        from raft_tpu.serve import BucketGrid, Searcher, warmup

        jax.config.update("jax_compilation_cache_dir", None)
        db = np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32)
        report = warmup(Searcher.brute_force(db), BucketGrid((1, 2), (1,)))
        assert report["shapes"] == 2
        assert jax.config.jax_compilation_cache_dir is None
