"""Tuning-cache behavior: round-trip, versioning, corruption
fall-through, warm-cache short-circuit, and legality invariants."""

import json

import jax.numpy as jnp
import pytest

from repro.kernels import tuning
from repro.kernels.common import (
    BLOCK_N_CANDIDATES,
    LANE,
    VMEM_BUDGET,
    pick_block_n,
)

DIMS = {"dp": 1024, "kp": 128, "m": 8, "g": 1, "nb": 4096}


def _bytes_flat(bn: int) -> int:
    # Plenty of headroom: every ladder candidate fits.
    return 4 * (1024 * bn + 3 * bn)


def _run(bn: int):
    # Stand-in for a wrapper launch: cost independent of bn, device-free.
    return jnp.zeros((4,)) + bn


@pytest.fixture()
def cache_file(tmp_path, monkeypatch):
    path = tmp_path / "tuning.json"
    monkeypatch.setenv(tuning.ENV_VAR, str(path))
    return path


class TestRoundTrip:
    def test_autotune_persists_and_tuned_block_n_reads_back(self, cache_file):
        winner = tuning.autotune("k", "f32", DIMS, _run, _bytes_flat)
        assert cache_file.exists()
        got = tuning.tuned_block_n("k", "f32", DIMS, _bytes_flat)
        assert got == winner
        payload = json.loads(cache_file.read_text())
        assert payload["version"] == tuning.SCHEMA_VERSION

    def test_keys_separate_precision_and_dims(self, cache_file):
        tuning._store_entry(tuning.shape_key("k", "f32", DIMS), 256, 1.0)
        assert tuning.cached_block_n("k", "f32", DIMS) == 256
        assert tuning.cached_block_n("k", "bf16", DIMS) is None
        assert tuning.cached_block_n("k", "f32", {**DIMS, "dp": 2048}) is None

    def test_bucket_n_is_block_independent(self):
        # nb buckets on the largest ladder candidate so the key cannot
        # depend on the chosen block size.
        assert tuning.bucket_n(1) == max(tuning.DEFAULT_TUNE_CANDIDATES)
        assert tuning.bucket_n(1025) == 2 * max(tuning.DEFAULT_TUNE_CANDIDATES)


class TestFallThrough:
    def test_missing_file_falls_back_to_pick_block_n(self, cache_file):
        assert not cache_file.exists()
        expect = pick_block_n(_bytes_flat)
        assert tuning.tuned_block_n("k", "f32", DIMS, _bytes_flat) == expect

    def test_corrupted_file_falls_back(self, cache_file):
        cache_file.write_text("{not json")
        expect = pick_block_n(_bytes_flat)
        assert tuning.tuned_block_n("k", "f32", DIMS, _bytes_flat) == expect

    def test_stale_schema_version_falls_back(self, cache_file):
        key = tuning.shape_key("k", "f32", DIMS)
        kind = tuning.device_kind()
        cache_file.write_text(
            json.dumps(
                {
                    "version": tuning.SCHEMA_VERSION + 1,
                    "entries": {kind: {key: {"block_n": 512, "us_per_call": 1.0}}},
                }
            )
        )
        expect = pick_block_n(_bytes_flat)
        assert tuning.tuned_block_n("k", "f32", DIMS, _bytes_flat) == expect

    def test_oversubscribing_entry_is_rejected(self, cache_file):
        # A cached winner that no longer fits the wrapper's CURRENT
        # budget formula must not be honored.
        tuning._store_entry(tuning.shape_key("k", "f32", DIMS), 1024, 1.0)
        tight = lambda bn: 16 * 1024 * bn  # 1024 → 16 MiB blows VMEM_BUDGET
        got = tuning.tuned_block_n("k", "f32", DIMS, tight)
        assert tight(got) <= VMEM_BUDGET
        assert got == pick_block_n(tight)

    def test_non_lane_multiple_entry_is_rejected(self, cache_file):
        tuning._store_entry(tuning.shape_key("k", "f32", DIMS), 100, 1.0)
        assert tuning.tuned_block_n("k", "f32", DIMS, _bytes_flat) == pick_block_n(
            _bytes_flat
        )


class TestOptIn:
    """No file decides a block size unless $REPRO_TUNING_CACHE names it,
    and entries of one device kind never serve another."""

    def test_home_cache_file_is_never_read(self, tmp_path, monkeypatch):
        monkeypatch.delenv(tuning.ENV_VAR, raising=False)
        monkeypatch.setenv("HOME", str(tmp_path))
        stray = tmp_path / ".cache" / "repro" / "tuning.json"
        stray.parent.mkdir(parents=True)
        key = tuning.shape_key("k", "f32", DIMS)
        stray.write_text(json.dumps({
            "version": tuning.SCHEMA_VERSION,
            "entries": {tuning.device_kind(): {key: {"block_n": 128,
                                                     "us_per_call": 1.0}}},
        }))
        assert tuning.cache_path() is None
        assert tuning.cached_block_n("k", "f32", DIMS) is None
        assert tuning.tuned_block_n("k", "f32", DIMS, _bytes_flat) == (
            pick_block_n(_bytes_flat))

    def test_autotune_without_cache_file_refuses(self, monkeypatch):
        monkeypatch.delenv(tuning.ENV_VAR, raising=False)
        before = tuning.measurement_runs()
        with pytest.raises(ValueError, match=tuning.ENV_VAR):
            tuning.autotune("k", "f32", DIMS, _run, _bytes_flat)
        assert tuning.measurement_runs() == before

    def test_entries_are_keyed_by_device_kind(self, cache_file):
        tuning._store_entry(tuning.shape_key("k", "f32", DIMS), 256, 1.0)
        payload = json.loads(cache_file.read_text())
        assert list(payload["entries"]) == [tuning.device_kind()]
        other = {"other chip": payload["entries"].pop(tuning.device_kind())}
        cache_file.write_text(json.dumps({**payload, "entries": other}))
        assert tuning.cached_block_n("k", "f32", DIMS) is None


class TestWarmCache:
    def test_second_autotune_performs_zero_measurements(self, cache_file):
        tuning.autotune("k", "f32", DIMS, _run, _bytes_flat)
        before = tuning.measurement_runs()
        again = tuning.autotune("k", "f32", DIMS, _run, _bytes_flat)
        assert tuning.measurement_runs() == before  # zero new runs
        assert again == tuning.cached_block_n("k", "f32", DIMS)

    def test_force_remeasures(self, cache_file):
        tuning.autotune("k", "f32", DIMS, _run, _bytes_flat)
        before = tuning.measurement_runs()
        tuning.autotune("k", "f32", DIMS, _run, _bytes_flat, force=True)
        assert tuning.measurement_runs() > before

    def test_external_rewrite_invalidates_memo(self, cache_file):
        tuning.autotune("k", "f32", DIMS, _run, _bytes_flat)
        assert tuning.cached_block_n("k", "f32", DIMS) is not None
        cache_file.write_text("garbage")  # corruption after a good load
        assert tuning.cached_block_n("k", "f32", DIMS) is None


class TestLegalityProperty:
    def test_cached_choice_is_lane_legal_and_fits_vmem(self, tmp_path, monkeypatch):
        # Whatever garbage lands in the cache (any positive int), the
        # block size the wrappers actually use is a LANE multiple that
        # fits VMEM_BUDGET under the stated byte formula.
        hypothesis = pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        monkeypatch.setenv(tuning.ENV_VAR, str(tmp_path / "tuning.json"))

        @settings(max_examples=25, deadline=None)
        @given(
            dp=st.integers(8, 4096).map(lambda v: ((v + 7) // 8) * 8),
            rest=st.integers(0, 1 << 20),
            seed_bn=st.integers(1, 2048),
        )
        def prop(dp, rest, seed_bn):
            vmem = lambda bn: 4 * (dp * bn + rest)
            dims = {"dp": dp, "nb": tuning.bucket_n(seed_bn)}
            tuning._store_entry(tuning.shape_key("k", "f32", dims), seed_bn, 1.0)
            got = tuning.tuned_block_n("k", "f32", dims, vmem)
            assert got % LANE == 0
            assert vmem(got) <= VMEM_BUDGET or got == min(BLOCK_N_CANDIDATES)

        prop()
