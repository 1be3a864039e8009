"""Environment / compatibility report (the ``dstpu_report`` command).

TPU-native counterpart of the reference's ``ds_report`` (env_report.py:125:
op compatibility matrix + version/platform info). Ops here are Pallas
kernels and XLA paths rather than JIT-compiled CUDA extensions, so the
compat column reports backend availability instead of nvcc/ABI checks.
"""

import sys


def _ver(mod_name: str) -> str:
    try:
        mod = __import__(mod_name)
        return getattr(mod, "__version__", "unknown")
    except ImportError:
        return "not installed"


def _dist_ver(dist_name: str) -> str:
    """Version from package metadata (for namespace packages like orbax)."""
    try:
        from importlib.metadata import version

        return version(dist_name)
    except Exception:
        return "not installed"


def op_compatibility():
    """(name, available, note) rows for the op inventory (SURVEY §2.4 map)."""
    rows = []
    try:
        import jax

        platform = jax.devices()[0].platform
    except Exception:
        platform = "none"
    on_tpu = platform == "tpu"
    rows.append(("flash_attention (pallas)", True, "TPU kernel; XLA fallback elsewhere"))
    rows.append(("block_sparse_attention (pallas)", True, "TPU kernel; XLA fallback elsewhere"))
    rows.append(("quantizer ops", True, "jnp everywhere"))
    rows.append(("fused_adam / fused_lamb", True, "whole-pytree jit"))
    rows.append(("1-bit optimizers", True, "int8 wire over shard_map"))
    rows.append(("ring / ulysses sequence parallel", True, "shard_map collectives"))
    try:
        import orbax.checkpoint  # noqa: F401

        rows.append(("orbax checkpoint engine", True, ""))
    except ImportError:
        rows.append(("orbax checkpoint engine", False, "pip install orbax-checkpoint"))
    rows.append(("tpu backend", on_tpu, f"current platform: {platform}"))
    return rows


def main():
    import jax

    print("-" * 64)
    print("deepspeed_tpu environment report (reference: ds_report)")
    print("-" * 64)
    print(f"python ................ {sys.version.split()[0]}")
    print(f"jax ................... {_ver('jax')}")
    print(f"jaxlib ................ {_ver('jaxlib')}")
    print(f"orbax-checkpoint ...... {_dist_ver('orbax-checkpoint')}")
    print(f"numpy ................. {_ver('numpy')}")
    print(f"deepspeed_tpu ......... {_ver('deepspeed_tpu')}")
    print("-" * 64)
    try:
        devs = jax.devices()
        print(f"devices ............... {len(devs)} x {devs[0].device_kind} ({devs[0].platform})")
        print(f"process count ......... {jax.process_count()}")
    except Exception as e:
        print(f"devices ............... unavailable ({e})")
    print("-" * 64)
    print(f"{'op name':<36} {'compatible':<12} note")
    for name, ok, note in op_compatibility():
        print(f"{name:<36} {'[YES]' if ok else '[NO]':<12} {note}")
    print("-" * 64)


if __name__ == "__main__":
    main()
