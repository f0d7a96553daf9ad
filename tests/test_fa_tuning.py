"""Flash-attention tuning table: resolution rules + sweep plumbing."""

import json

import pytest

from dlrover_tpu.ops.pallas import tuning


@pytest.fixture(autouse=True)
def isolated_tables(monkeypatch, tmp_path):
    """No shipped/env table leaks into (or out of) a test."""
    monkeypatch.setattr(tuning, "_SHIPPED", str(tmp_path / "shipped.json"))
    monkeypatch.delenv("DLROVER_TPU_FA_TUNING", raising=False)
    tuning._load_one.cache_clear()
    yield tmp_path
    tuning._load_one.cache_clear()


class TestTunedBlocks:
    def test_default_divides_sequence(self):
        assert tuning.tuned_blocks(2048, 128) == (512, 512)
        # 384 = 3*128: 512 does not divide; must shrink to a divisor
        block_q, block_kv = tuning.tuned_blocks(384, 128)
        assert 384 % block_q == 0 and 384 % block_kv == 0

    def test_exact_table_hit(self, monkeypatch, isolated_tables):
        path = isolated_tables / "t.json"
        path.write_text(json.dumps({
            "s2048_d128": {"block_q": 1024, "block_kv": 256},
        }))
        monkeypatch.setenv("DLROVER_TPU_FA_TUNING", str(path))
        assert tuning.tuned_blocks(2048, 128) == (1024, 256)

    def test_env_table_overrides_shipped(self, monkeypatch, isolated_tables):
        (isolated_tables / "shipped.json").write_text(json.dumps({
            "s1024_d64": {"block_q": 512, "block_kv": 512},
        }))
        (isolated_tables / "env.json").write_text(json.dumps({
            "s1024_d64": {"block_q": 256, "block_kv": 128},
        }))
        tuning._load_one.cache_clear()
        assert tuning.tuned_blocks(1024, 64) == (512, 512)
        monkeypatch.setenv(
            "DLROVER_TPU_FA_TUNING", str(isolated_tables / "env.json")
        )
        assert tuning.tuned_blocks(1024, 64) == (256, 128)

    def test_no_per_user_table_is_read(self, monkeypatch, isolated_tables):
        """The table is the shipped file plus the env file: a file under
        the home directory must change nothing."""
        home = isolated_tables / "home"
        cache = home / ".cache" / "dlrover_tpu"
        cache.mkdir(parents=True)
        (cache / "fa_tuned.json").write_text(json.dumps({
            "s1024_d64": {"block_q": 128, "block_kv": 128},
        }))
        monkeypatch.setenv("HOME", str(home))
        tuning._load_one.cache_clear()
        assert tuning.tuned_blocks(1024, 64) == (512, 512)

    def test_autotune_without_a_target_refuses(self):
        with pytest.raises(RuntimeError, match="DLROVER_TPU_FA_TUNING"):
            tuning.autotune(256, 64, require_tpu=False)

    def test_nearest_seq_borrow_shrinks_to_divisor(
        self, monkeypatch, isolated_tables
    ):
        path = isolated_tables / "t.json"
        path.write_text(json.dumps({
            "s4096_d128": {"block_q": 1024, "block_kv": 1024},
        }))
        monkeypatch.setenv("DLROVER_TPU_FA_TUNING", str(path))
        for seq in (1536, 192):  # 3*512 and 3*64
            block_q, block_kv = tuning.tuned_blocks(seq, 128)
            assert seq % block_q == 0 and seq % block_kv == 0, (
                seq, block_q, block_kv,
            )
        # other head dims never borrowed
        assert tuning.tuned_blocks(4096, 64) == (512, 512)

    def test_malformed_table_degrades_to_default(
        self, monkeypatch, isolated_tables
    ):
        """A hand-edited table (bad keys, zero blocks, wrong types) must
        fall back to defaults — never crash the forward pass."""
        path = isolated_tables / "bad.json"
        path.write_text(json.dumps({
            "default_d128": {"block_q": 512, "block_kv": 512},  # bad key
            "s1024_d128": {"block_q": 0, "block_kv": 512},      # zero
            "s512_d64": {"block_q": "big", "block_kv": 128},    # type
            "s256_d32": "not-a-dict",
        }))
        monkeypatch.setenv("DLROVER_TPU_FA_TUNING", str(path))
        tuning._load_one.cache_clear()
        assert tuning.tuned_blocks(2048, 128) == (512, 512)
        assert tuning.tuned_blocks(1024, 128) == (512, 512)
        assert tuning.tuned_blocks(512, 64) == (512, 512)
        assert tuning.tuned_blocks(256, 32) == (256, 256)

    def test_candidates_divide(self):
        for block_q, block_kv in tuning._candidates(1536):
            assert 1536 % block_q == 0 and 1536 % block_kv == 0

    def test_autotune_refuses_cpu(self):
        import jax

        if jax.default_backend() == "tpu":
            pytest.skip("refusal check only applies off-TPU")
        with pytest.raises(RuntimeError, match="TPU backend"):
            tuning.autotune(256, 64)

    def test_autotune_writes_env_table_on_cpu_interpret(
        self, monkeypatch, isolated_tables
    ):
        """The sweep plumbing itself (candidate loop, persist, reload) is
        testable with require_tpu=False on the CPU interpreter at tiny
        size; timings are meaningless and never shipped."""
        import jax

        if jax.default_backend() == "tpu":
            pytest.skip("covered by the real sweep on TPU")
        import dlrover_tpu.ops.pallas.flash_attention as fa_mod

        real = fa_mod.pallas_flash_attention

        def interp(q, k, v, **kw):
            return real(q, k, v, interpret=True, **kw)

        monkeypatch.setattr(
            tuning, "_candidates", lambda s: [(128, 128), (256, 256)]
        )
        monkeypatch.setattr(fa_mod, "pallas_flash_attention", interp)
        # no out_path: must land where the env points, never the package
        monkeypatch.setenv(
            "DLROVER_TPU_FA_TUNING", str(isolated_tables / "user.json")
        )
        entry = tuning.autotune(
            256, 64, heads=2, batch=1, require_tpu=False
        )
        assert entry["block_q"] in (128, 256)
        table = json.loads(open(str(isolated_tables / "user.json")).read())
        assert "s256_d64" in table
        assert not (isolated_tables / "shipped.json").exists()
        tuning._load_one.cache_clear()
        assert tuning.tuned_blocks(256, 64) == (
            entry["block_q"], entry["block_kv"]
        )
