"""Backend registry: resolution precedence (config > env > platform),
registration invariants, shard-impl mapping, and the env-var lint."""

import os
import sys

import pytest

from repro.core import distributed
from repro.kernels import ops, registry
from repro.kernels.registry import Backend, BackendSpec

ENV = registry.ENV_VAR


def test_precedence_config_beats_env(monkeypatch):
    monkeypatch.setenv(ENV, "xla")
    bk = registry.resolve_backend("numpy")
    assert bk.name == "numpy" and bk.source == "config"


def test_precedence_env_beats_platform(monkeypatch):
    monkeypatch.setenv(ENV, "pallas")
    bk = registry.resolve_backend(None)
    assert bk.name == "pallas" and bk.source == "env"


def test_precedence_platform_default(monkeypatch):
    monkeypatch.delenv(ENV, raising=False)
    bk = registry.resolve_backend(None)
    assert bk.name == registry.platform_default() and bk.source == "platform"
    assert registry.platform_default("tpu") == "fused-gather"
    assert registry.platform_default("cpu") == "auto"
    assert registry.resolve_backend(None, platform="tpu").name == "fused-gather"


def test_resolved_backend_passes_through():
    bk = Backend("fused", source="env")
    assert registry.resolve_backend(bk) is bk


def test_unknown_config_name_raises_unknown_env_degrades(monkeypatch):
    with pytest.raises(ValueError, match="unknown filter backend"):
        registry.resolve_backend("cuda")
    # a typo'd env var raises too: silently serving the platform default
    # would hide which filter path a run measured
    monkeypatch.setenv(ENV, "cudnn")
    with pytest.raises(ValueError, match=ENV):
        registry.resolve_backend(None)
    monkeypatch.setenv(ENV, "")  # unset-equivalent: the platform default
    assert registry.resolve_backend(None).source == "platform"


def test_backend_properties():
    assert Backend("fused").fused and Backend("fused").device
    assert not Backend("pallas").fused
    assert not Backend("numpy").device
    assert str(Backend("xla")) == "xla"
    assert set(registry.backend_names()) == {
        "fused", "fused-gather", "pallas", "xla", "numpy", "auto"
    }
    # fused-gather is a fused backend (counts-only) that ALSO gathers on
    # device; plain fused must not claim the gather capability
    gb = Backend("fused-gather")
    assert gb.fused and gb.device and gb.gather
    assert not Backend("fused").gather


def test_register_backend_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        registry.register_backend(BackendSpec("fused", "dup"))


def test_fused_filter_default_follows_registry(monkeypatch):
    monkeypatch.setenv(ENV, "fused")
    assert ops.fused_filter_default()
    monkeypatch.setenv(ENV, "xla")
    assert not ops.fused_filter_default()


def test_shard_impl_mapping(monkeypatch):
    # shard-impl names pass through; registry backends map fused/composed
    assert distributed.shard_impl_for("blocked") == "blocked"
    assert distributed.shard_impl_for("broadcast") == "broadcast"
    assert distributed.shard_impl_for("fused") == "fused"
    assert distributed.shard_impl_for(Backend("fused")) == "fused"
    # gather-fused is a fused-family backend: the sharded filter runs its
    # fused (host-gather) shard impl — and the demotion is VISIBLE now
    # (debug log + stats counter; routed ShardedMateIndex keeps the
    # gather-fused launch shard-local instead)
    assert distributed.shard_impl_for(Backend("fused-gather")) == "fused"
    assert distributed.shard_impl_for(Backend("xla")) == "broadcast"
    monkeypatch.setenv(ENV, "fused")
    assert distributed.shard_impl_for(None) == "fused"
    monkeypatch.delenv(ENV)
    assert distributed.shard_impl_for(None) == (
        "fused" if registry.platform_default() == "fused" else "broadcast"
    )


def test_shard_impl_gather_demotion_is_visible(caplog):
    """shard_impl_for silently demoted fused-gather to the fused shard impl;
    now it debug-logs the demotion and bumps the passed stats counter."""
    from repro.core.discovery import DiscoveryStats

    stats = DiscoveryStats()
    with caplog.at_level("DEBUG", logger="repro.core.distributed"):
        impl = distributed.shard_impl_for(Backend("fused-gather"), stats=stats)
    assert impl == "fused"
    assert stats.shard_gather_demotions == 1
    assert any("demoting" in r.message for r in caplog.records)
    # non-gather backends: no demotion, counter untouched
    with caplog.at_level("DEBUG", logger="repro.core.distributed"):
        assert distributed.shard_impl_for(Backend("fused"), stats=stats) == "fused"
    assert stats.shard_gather_demotions == 1


def test_env_var_read_only_by_registry():
    """The CI lint's contract, enforced as a tier-1 test too: no module
    outside kernels/registry.py reads MATE_FILTER_BACKEND."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    try:
        from tools.lint_backend_env import violations
    finally:
        sys.path.remove(repo)
    assert violations(repo) == []


def test_lint_catches_real_reads():
    """The lint must flag code-level reads while letting docstrings and
    comments document the env var."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    try:
        from tools.lint_backend_env import reads_env_var
    finally:
        sys.path.remove(repo)
    needle = "MATE_FILTER" + "_BACKEND"
    assert reads_env_var(f'import os\nx = os.environ.get("{needle}")\n')
    assert reads_env_var(f'FLAG = "{needle}"\n')
    assert not reads_env_var(f'"""docs mention {needle} here"""\nx = 1\n')
    assert not reads_env_var(f"# comment about {needle}\nx = 1\n")
    assert not reads_env_var(
        f'def f():\n    """{needle} docs."""\n    return 0\n'
    )
