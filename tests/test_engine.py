"""JaxEngine + bucket policy + HBM manager tests (CPU backend)."""

import os

import numpy as np
import pytest

from kfserving_tpu.engine import BucketPolicy, JaxEngine
from kfserving_tpu.engine.hbm import HBMManager, InsufficientHBM


class TestBucketPolicy:
    def test_pow2(self):
        assert BucketPolicy.pow2(32).buckets == [1, 2, 4, 8, 16, 32]
        assert BucketPolicy.pow2(48).buckets == [1, 2, 4, 8, 16, 32, 48]

    def test_fit(self):
        p = BucketPolicy([1, 4, 16])
        assert p.fit(1) == 1
        assert p.fit(3) == 4
        assert p.fit(16) == 16
        assert p.fit(17) is None

    def test_waste(self):
        p = BucketPolicy([8])
        assert p.waste(6) == pytest.approx(0.25)


def make_engine(**kw):
    import jax.numpy as jnp

    # y = x @ W with a known W: predictions are deterministic.
    W = np.arange(12, dtype=np.float32).reshape(3, 4)

    def apply_fn(params, x):
        return jnp.dot(x, params["w"])

    return JaxEngine(apply_fn, {"w": W},
                     batch_buckets=BucketPolicy([1, 2, 4, 8]), **kw), W


class TestJaxEngine:
    async def test_predict_matches_numpy(self):
        engine, W = make_engine()
        x = np.random.RandomState(0).randn(3, 3).astype(np.float32)
        out = await engine.predict(x)
        np.testing.assert_allclose(out, x @ W, rtol=1e-5)
        assert out.shape == (3, 4)  # un-padded back to 3 from bucket 4

    async def test_batch_exceeds_buckets(self):
        engine, _ = make_engine()
        with pytest.raises(ValueError, match="exceeds the largest"):
            await engine.predict(np.zeros((9, 3), np.float32))

    async def test_dict_inputs(self):
        import jax.numpy as jnp

        def apply_fn(params, batch):
            return batch["a"] + batch["b"] * params["s"]

        engine = JaxEngine(apply_fn, {"s": np.float32(2.0)},
                           batch_buckets=BucketPolicy([4]))
        out = await engine.predict({
            "a": np.ones((2, 3), np.float32),
            "b": np.ones((2, 3), np.float32),
        })
        np.testing.assert_allclose(out, np.full((2, 3), 3.0))

    def test_warmup_compiles_all_buckets(self):
        engine, _ = make_engine()
        secs = engine.warmup(np.zeros((3,), np.float32))
        assert secs >= 0
        assert engine.compile_count == 4
        # After warmup, execution reuses the cached executables.
        out = engine.predict_sync(np.zeros((5, 3), np.float32))
        assert out.shape == (5, 4)

    def test_warmup_minimal_only_largest_bucket(self):
        """Recycle-successor mode: warm the largest bucket only; the
        rest load on demand from the persistent cache, instead of
        the full grid running inside the successor's load time."""
        engine, _ = make_engine()
        engine.warmup(np.zeros((3,), np.float32), minimal=True)
        assert engine.compile_count == 1
        # Smaller buckets still serve (on-demand compile).
        out = engine.predict_sync(np.zeros((2, 3), np.float32))
        assert out.shape == (2, 4)

    def test_seq_buckets(self):
        import jax.numpy as jnp

        def apply_fn(params, x):
            return jnp.sum(x, axis=-1)

        engine = JaxEngine(apply_fn, {},
                           batch_buckets=BucketPolicy([4]),
                           seq_buckets=BucketPolicy([8, 16]))
        out = engine.predict_sync(np.ones((2, 5), np.float32))
        # padded to seq 8 with zeros → sums unchanged; sliced back to 2 rows
        np.testing.assert_allclose(out, [5.0, 5.0])

    def test_param_bytes(self):
        engine, W = make_engine()
        assert engine.param_bytes() == W.nbytes

    def test_dtype_cast(self):
        import ml_dtypes

        engine, W = make_engine(dtype=ml_dtypes.bfloat16)
        out = engine.predict_sync(np.ones((1, 3), np.float32))
        # bf16 matmul of small ints is exact
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.ones((1, 3)) @ W)


class TestHBMManager:
    def test_admit_within_budget(self):
        m = HBMManager(budget_bytes=100)
        assert m.admit("a", 60) == []
        assert m.used_bytes == 60
        assert m.free_bytes == 40

    def test_eviction_lru(self):
        evicted_names = []
        m = HBMManager(budget_bytes=100, evict_cb=evicted_names.append)
        m.admit("a", 60)
        m.admit("b", 30)
        evicted = m.admit("c", 50)  # needs 50, only 10 free → evict a (LRU)
        assert evicted == ["a"] == evicted_names
        assert set(m.resident_models()) == {"b", "c"}

    def test_touch_changes_lru_order(self):
        m = HBMManager(budget_bytes=100)
        m.admit("a", 50)
        m.admit("b", 40)
        m.touch("a")  # now b is LRU
        evicted = m.admit("c", 50)
        assert evicted == ["b"]

    def test_too_big_for_budget(self):
        m = HBMManager(budget_bytes=100)
        with pytest.raises(InsufficientHBM):
            m.admit("huge", 200)

    def test_no_evict_mode(self):
        m = HBMManager(budget_bytes=100)
        m.admit("a", 80)
        with pytest.raises(InsufficientHBM):
            m.admit("b", 50, evict=False)
        assert m.resident_models() == ["a"]

    def test_release(self):
        m = HBMManager(budget_bytes=100)
        m.admit("a", 80)
        m.release("a")
        assert m.used_bytes == 0

    def test_commit_replaces_atomically(self):
        """Reload commit: staging entry becomes the model's entry with the
        measured size; no release/re-admit window for a concurrent admit
        to exploit."""
        m = HBMManager(budget_bytes=100)
        m.admit("a", 40)
        m.admit("a!staging", 40, evict=False)
        m.commit("a!staging", "a", nbytes=45)
        assert m.resident_models() == ["a"]
        assert m.used_bytes == 45
        # freed headroom is claimable only AFTER commit
        m.admit("b", 55, evict=False)
        assert m.used_bytes == 100

    def test_commit_without_staging_keeps_entry(self):
        m = HBMManager(budget_bytes=100)
        m.admit("a", 40)
        m.commit("a!staging", "a")  # staging missing: keep current books
        assert m.used_bytes == 40


def test_hbm_readmit_replaces_old_entry():
    """Re-admitting a resident model replaces its accounting entry instead of
    double-counting it or spuriously evicting others."""
    from kfserving_tpu.engine.hbm import HBMManager

    m = HBMManager(budget_bytes=100)
    m.admit("a", 60)
    evicted = m.admit("a", 60)  # reload: must fit by replacing itself
    assert evicted == []
    assert m.used_bytes == 60
    m.admit("b", 40)
    assert sorted(m.resident_models()) == ["a", "b"]


def test_hbm_failed_admit_restores_books():
    """A failed admit must leave accounting untouched (no phantom free)."""
    import pytest

    from kfserving_tpu.engine.hbm import HBMManager, InsufficientHBM

    m = HBMManager(budget_bytes=100)
    m.admit("a", 60)
    m.admit("b", 30)
    with pytest.raises(InsufficientHBM):
        m.admit("a", 80, evict=False)
    assert m.used_bytes == 90
    assert sorted(m.resident_models()) == ["a", "b"]


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_jax_cache_config(self):
        """These tests point the process-global JAX cache config at
        pytest tmp dirs; restore it so later compilations in this
        process don't write into deleted directories."""
        import jax

        saved_dir = jax.config.jax_compilation_cache_dir
        saved_min = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_compilation_cache_dir", saved_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved_min)

    def test_env_var_is_left_to_jax(self, tmp_path, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: JAX's own handling stands,
        enable() sets no directory in code."""
        import jax

        from kfserving_tpu.engine import compile_cache

        d = str(tmp_path / "envcache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert compile_cache.enable() == "sentinel"
        assert jax.config.jax_compilation_cache_dir == "sentinel"

    def test_unset_env_picks_fixed_checkout_path(self, monkeypatch):
        import jax

        from kfserving_tpu.engine import compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        expected = os.path.join(repo, ".kfs_cache", "xla")
        assert compile_cache.enable(min_compile_time_secs=0.0) == expected
        assert jax.config.jax_compilation_cache_dir == expected
        assert os.path.isdir(expected)
        # fixed: a second call (any process, any time) names the same dir
        assert compile_cache.enable() == expected

    def test_enable_sets_threshold_and_gauge(self, tmp_path, monkeypatch):
        import jax

        from kfserving_tpu.engine import compile_cache
        from kfserving_tpu.observability import REGISTRY

        d = str(tmp_path / "envcache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
        jax.config.update("jax_compilation_cache_dir", d)
        assert compile_cache.enable(min_compile_time_secs=0.25) == d
        assert jax.config.jax_persistent_cache_min_compile_time_secs \
            == 0.25
        assert "kfserving_tpu_compile_cache_enabled" in \
            REGISTRY.sample_names()
