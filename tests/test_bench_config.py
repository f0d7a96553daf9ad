"""Checkpoint-bench config selection: the async-snapshot HBM envelope
and the transfer-time budget must pick an honest config (a state too
big for the transient device copy would silently measure the sync
fallback instead of the dispatch-only save)."""

from dlrover_tpu.trainer.flash_checkpoint.bench import pick_ckpt_config


class TestPickCkptConfig:
    def test_fast_link_big_hbm_picks_largest(self):
        tag, cfg, B, S, note = pick_ckpt_config(
            budget_s=1500, bw_gbps=10.0, hbm_gb=16.0
        )
        assert tag == "llama-0.7B"
        assert "projected" in note

    def test_slow_link_picks_smaller(self):
        # 0.02 GB/s link: 0.8B would need 3*6.6GB/0.02 ~= 1000s... per
        # leg; the 350M config is the one that fits a 900s budget
        tag, cfg, B, S, note = pick_ckpt_config(
            budget_s=420, bw_gbps=0.02, hbm_gb=16.0
        )
        assert tag == "llama-350M"

    def test_tiny_hbm_respects_envelope(self):
        # 8GB HBM: 0.8B state (6.6GB) + copy would not fit
        tag, cfg, B, S, note = pick_ckpt_config(
            budget_s=10_000, bw_gbps=10.0, hbm_gb=8.0
        )
        assert tag == "llama-350M"

    def test_impossible_budget_falls_back_to_smallest(self):
        tag, cfg, B, S, note = pick_ckpt_config(
            budget_s=1, bw_gbps=0.001, hbm_gb=16.0
        )
        assert tag == "llama-350M"
        assert "fallback" in note
