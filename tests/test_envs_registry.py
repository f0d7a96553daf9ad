"""Typed env-knob registry (dlrover_tpu.common.envs) tests."""

import os

import pytest

from dlrover_tpu.common import envs
from dlrover_tpu.common.constants import NodeEnv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestRegistry:
    def test_every_knob_has_type_default_and_doc(self):
        knobs = envs.all_knobs()
        assert len(knobs) >= 80
        for k in knobs:
            assert k.type in ("str", "int", "float", "bool"), k.name
            assert k.doc.strip(), f"{k.name} has no doc"
            expected = {"str": str, "int": int, "float": float,
                        "bool": bool}[k.type]
            assert isinstance(k.default, expected), \
                f"{k.name}: default {k.default!r} is not {k.type}"

    def test_node_env_constants_are_registered(self):
        names = set(envs.all_knob_names())
        for attr in vars(NodeEnv):
            if attr.startswith("_"):
                continue
            assert getattr(NodeEnv, attr) in names, attr

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError):
            envs.register(NodeEnv.JOB_NAME, "str", "", "dup")

    def test_unregistered_name_raises(self):
        with pytest.raises(KeyError):
            envs.get_str("DLROVER_TPU_NO_SUCH_KNOB")

    def test_type_mismatch_is_a_programming_error(self):
        with pytest.raises(AssertionError):
            envs.get_int(NodeEnv.JOB_NAME)  # registered as str


class TestTypedReads:
    def test_defaults_when_unset(self, monkeypatch):
        monkeypatch.delenv(NodeEnv.NUM_PROCESSES, raising=False)
        monkeypatch.delenv("DLROVER_TPU_STAGE_FACTOR", raising=False)
        monkeypatch.delenv("DLROVER_TPU_DIST_DIFF", raising=False)
        assert envs.get_int(NodeEnv.NUM_PROCESSES) == 1
        assert envs.get_float("DLROVER_TPU_STAGE_FACTOR") == 1.5
        assert envs.get_bool("DLROVER_TPU_DIST_DIFF") is True

    def test_reads_are_live_not_import_frozen(self, monkeypatch):
        monkeypatch.setenv(NodeEnv.NUM_PROCESSES, "8")
        assert envs.get_int(NodeEnv.NUM_PROCESSES) == 8
        monkeypatch.setenv(NodeEnv.NUM_PROCESSES, "2")
        assert envs.get_int(NodeEnv.NUM_PROCESSES) == 2

    def test_per_call_default_override(self, monkeypatch):
        monkeypatch.delenv(NodeEnv.NODE_ID, raising=False)
        assert envs.get_int(NodeEnv.NODE_ID, default=7) == 7
        monkeypatch.setenv(NodeEnv.NODE_ID, "3")
        assert envs.get_int(NodeEnv.NODE_ID, default=7) == 3

    def test_malformed_value_falls_back(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_STAGE_FACTOR", "not-a-float")
        assert envs.get_float("DLROVER_TPU_STAGE_FACTOR") == 1.5
        monkeypatch.setenv(NodeEnv.NUM_PROCESSES, "")
        assert envs.get_int(NodeEnv.NUM_PROCESSES) == 1

    def test_int_accepts_scientific_byte_sizes(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_ASYNC_MIN_BYTES", "1e8")
        assert envs.get_int("DLROVER_TPU_ASYNC_MIN_BYTES") == 100_000_000

    def test_bool_parsing(self, monkeypatch):
        for raw, expect in [("1", True), ("true", True), ("YES", True),
                            ("on", True), ("0", False), ("false", False),
                            ("off", False), ("", False)]:
            monkeypatch.setenv("DLROVER_TPU_NETWORK_CHECK", raw)
            assert envs.get_bool("DLROVER_TPU_NETWORK_CHECK") is expect, raw

    def test_bool_malformed_value_falls_back_to_default(self, monkeypatch):
        """Regression: a typo like PRE_CHECK=enabled must not silently
        disable a default-on feature — it warns and keeps the default."""
        monkeypatch.setenv("DLROVER_TPU_PRE_CHECK", "enabled")
        assert envs.get_bool("DLROVER_TPU_PRE_CHECK") is True  # default True
        monkeypatch.setenv("DLROVER_TPU_NETWORK_CHECK", "maybe")
        assert envs.get_bool("DLROVER_TPU_NETWORK_CHECK") is False

    def test_is_set_and_raw(self, monkeypatch):
        monkeypatch.delenv(NodeEnv.JOB_NAME, raising=False)
        assert not envs.is_set(NodeEnv.JOB_NAME)
        assert envs.raw(NodeEnv.JOB_NAME) is None
        monkeypatch.setenv(NodeEnv.JOB_NAME, "jobx")
        assert envs.is_set(NodeEnv.JOB_NAME)
        assert envs.raw(NodeEnv.JOB_NAME) == "jobx"

    def test_generic_get_dispatches_on_registered_type(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_PERSIST_WRITERS", "9")
        assert envs.get("DLROVER_TPU_PERSIST_WRITERS") == 9
        monkeypatch.setenv("DLROVER_TPU_VERIFY_CRC", "eager")
        assert envs.get("DLROVER_TPU_VERIFY_CRC") == "eager"


class TestDocsGeneration:
    def test_markdown_lists_every_knob(self):
        md = envs.render_markdown()
        for name in envs.all_knob_names():
            assert f"`{name}`" in md

    def test_docs_envs_md_is_in_sync(self):
        """docs/envs.md is generated from the registry; regenerate with
        `python -m dlrover_tpu.analysis --gen-env-docs docs/envs.md`."""
        path = os.path.join(REPO, "docs", "envs.md")
        with open(path, "r", encoding="utf-8") as f:
            on_disk = f.read()
        assert on_disk == envs.render_markdown(), (
            "docs/envs.md is stale; regenerate it"
        )
