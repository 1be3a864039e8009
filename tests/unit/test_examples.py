"""Every examples/ script must run end-to-end in smoke mode (the reference
keeps its examples out-of-repo in DeepSpeedExamples; here they ship and are
CI-exercised)."""

import os
import runpy
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "..", "examples")


@pytest.mark.parametrize(
    "script",
    ["train_gpt2.py", "bert_mlm.py",
     # the serving loop is unit-covered fast (test_continuous_batching);
     # the in-process example re-pays ~6 compiles cold
     pytest.param("serve_continuous.py", marks=pytest.mark.slow),
     # speculative + hybrid example flows are unit-covered fast in
     # test_speculative / test_hybrid_engine; the subprocess runs pay a
     # full jax import + compile each on the 1-core host
     pytest.param("inference_speculative.py", marks=pytest.mark.slow),
     # the rolling-cache mechanics are unit-covered fast in
     # test_rolling_cache; the example pays generate-program compiles
     pytest.param("serve_mistral_sliding.py", marks=pytest.mark.slow),
     pytest.param("rlhf_hybrid.py", marks=pytest.mark.slow)],
)
def test_example_runs(script, tmp_path, monkeypatch):
    from deepspeed_tpu import comm

    comm.destroy()
    # the examples place the persistent compile cache themselves
    # (utils/compile_cache.py); in-process they must keep THIS session's
    # own directory and threshold (conftest.py), not open <repo>/.jax_cache
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", jax.config.jax_compilation_cache_dir)
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("EXAMPLE_SMOKE", "1")
    monkeypatch.setenv("EXAMPLE_CKPT", str(tmp_path / "ck"))
    path = os.path.join(EXAMPLES, script)
    argv = sys.argv
    try:
        sys.argv = [path]
        runpy.run_path(path, run_name="__main__")
    finally:
        sys.argv = argv
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
