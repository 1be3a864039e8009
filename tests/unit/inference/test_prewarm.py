"""dstpu_prewarm CLI: precompile the serving program set into the
persistent XLA cache (cold-start cost on TPU is seconds to tens of seconds
per program; the reference ships prebuilt CUDA .so instead)."""

import os

import jax
import pytest

from deepspeed_tpu import comm

TINY = ["--override", "num_layers=2", "--override", "hidden_size=64",
        "--override", "num_heads=4", "--override", "vocab_size=128",
        "--override", "max_seq_len=64"]


@pytest.fixture
def restore_jax_cache_config():
    """prewarm main() redirects the global compile-cache config; later test
    modules must keep the conftest's shared cache."""
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    try:  # re-point the live cache instance back at the shared dir
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()
    except Exception:
        pass


def test_value_parsing():
    from deepspeed_tpu.inference.prewarm import _parse_value

    assert _parse_value("128") == 128
    assert _parse_value("0.125") == 0.125
    assert _parse_value("true") is True and _parse_value("False") is False
    assert _parse_value("none") is None
    assert _parse_value("rope") == "rope"


def test_prewarm_fused_only(tmp_path, restore_jax_cache_config):
    """FAST sibling: the CLI surface end-to-end on the tiny model, fused
    generate only (the chunk/continuous arms ride the same plumbing and
    are covered by the slow variant)."""
    from deepspeed_tpu.inference.prewarm import main

    comm.destroy()
    cache = str(tmp_path / "xla_cache")
    rc = main(["--batch", "1", "--prompt", "8", "--new", "2",
               "--dtype", "float32", "--cache-dir", cache, *TINY])
    assert rc == 0
    assert os.path.isdir(cache) and os.listdir(cache)


def test_prewarm_mesh_widths(tmp_path, restore_jax_cache_config):
    """--mesh 1:1,1:2 warms the program set once PER tensor width (a
    sharded executable is a distinct program — warming 1:1 does nothing
    for a 1:2 serve); both passes land in the same cache dir."""
    from deepspeed_tpu.inference.prewarm import main

    comm.destroy()
    cache = str(tmp_path / "xla_cache")
    rc = main(["--batch", "1", "--prompt", "8", "--new", "2",
               "--dtype", "float32", "--mesh", "1:1,1:2",
               "--cache-dir", cache, *TINY])
    assert rc == 0
    assert os.path.isdir(cache) and os.listdir(cache)


@pytest.mark.slow  # full serving program set (chunked + continuous pool)
def test_prewarm_full_set_persists(tmp_path, restore_jax_cache_config):
    from deepspeed_tpu.inference.prewarm import main

    comm.destroy()
    cache = str(tmp_path / "xla_cache")
    rc = main([
        "--batch", "1", "--prompt", "16", "--new", "4", "--dtype", "float32",
        "--chunk", "8", "--continuous", "--slots", "2", "--cache-len", "64",
        "--burst", "2", "--cache-dir", cache, *TINY,
    ])
    assert rc == 0
    assert os.path.isdir(cache) and len(os.listdir(cache)) >= 3, \
        os.listdir(cache) if os.path.isdir(cache) else "no cache dir"


def test_prewarm_audit_flag(tmp_path, restore_jax_cache_config, capsys):
    """--audit runs ds-audit over the captured program set at the end of
    the warm and exits 0 when the contracts hold. The fused-generate
    path has no capture site (not a registered family yet), so this
    fast sibling proves the CLI surface + clean exit; the continuous
    arm of the slow test below captures the real pool families."""
    from deepspeed_tpu.analysis.program import capture
    from deepspeed_tpu.inference.prewarm import main

    comm.destroy()
    cache = str(tmp_path / "xla_cache")
    rc = main(["--batch", "1", "--prompt", "8", "--new", "2",
               "--dtype", "float32", "--cache-dir", cache, "--audit", *TINY])
    assert rc == 0
    assert not capture.active()  # the hook was cleared on the way out
    assert "ds-audit over" in capsys.readouterr().out


@pytest.mark.slow  # continuous pool warm + a full audit of its programs
def test_prewarm_audit_captures_pool_programs(tmp_path,
                                              restore_jax_cache_config,
                                              capsys):
    from deepspeed_tpu.inference.prewarm import main

    comm.destroy()
    cache = str(tmp_path / "xla_cache")
    rc = main([
        "--batch", "1", "--prompt", "16", "--new", "4", "--dtype", "float32",
        "--continuous", "--slots", "2", "--cache-len", "64",
        "--cache-dir", cache, "--audit", *TINY,
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "ds-audit over" in out and "clean" in out
    # the pool warm built (and the audit therefore saw) real programs
    import re

    m = re.search(r"ds-audit over (\d+) captured", out)
    assert m and int(m.group(1)) > 0, out
