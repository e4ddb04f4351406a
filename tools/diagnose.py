"""Environment diagnosis (≙ reference tools/diagnose.py): prints the
platform, Python, key package versions, framework features, and device
visibility — what a bug report should include.

    python tools/diagnose.py
"""
import os
import platform
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _section(title):
    print(f"----------{title}----------")


def main():
    _section("Python Info")
    print(f"Version      : {platform.python_version()}")
    print(f"Compiler     : {platform.python_compiler()}")
    print(f"Build        : {platform.python_build()}")

    _section("Platform Info")
    print(f"Platform     : {platform.platform()}")
    print(f"system       : {platform.system()}")
    print(f"node         : {platform.node()}")
    print(f"release      : {platform.release()}")
    print(f"version      : {platform.version()}")
    print(f"cpu_count    : {os.cpu_count()}")
    try:
        print(f"loadavg      : {os.getloadavg()}")
    except OSError:
        pass

    _section("Environment")
    for k in sorted(os.environ):
        if k.startswith(("MXNET_", "JAX_", "XLA_", "TPU_", "LD_")):
            print(f"{k}={os.environ[k]}")

    _section("Package Versions")
    for mod in ("numpy", "scipy", "jax", "jaxlib", "flax", "optax",
                "orbax.checkpoint", "torch"):
        try:
            m = __import__(mod)
            print(f"{mod:<18}: {getattr(m, '__version__', '?')}")
        except ImportError:
            print(f"{mod:<18}: not installed")
        except Exception as e:   # broken install is a diagnosis, not a crash
            print(f"{mod:<18}: BROKEN ({type(e).__name__}: {e})")

    _section("Framework")
    t0 = time.time()
    import incubator_mxnet_tpu as mx
    print(f"import time  : {time.time() - t0:.3f} s")
    from incubator_mxnet_tpu.runtime import Features
    feats = Features()
    enabled = [k for k in feats.keys() if feats.is_enabled(k)] \
        if hasattr(feats, "is_enabled") and hasattr(feats, "keys") \
        else feats
    print(f"features     : {enabled}")

    _section("Devices")
    t0 = time.time()
    try:
        import jax
        if os.environ.get("DIAGNOSE_FORCE_CPU"):
            # hermetic-CI hook: keep the probe off the chip
            jax.config.update("jax_platforms", "cpu")
        devs = jax.devices()
        print(f"devices      : {[str(d) for d in devs]}")
        print(f"init time    : {time.time() - t0:.3f} s")
        a = mx.np.ones((128, 128))
        (a @ a).wait_to_read()
        print(f"matmul smoke : ok ({time.time() - t0:.3f} s total)")
    except Exception as e:  # a dead backend is exactly what we diagnose
        print(f"device init FAILED after {time.time() - t0:.1f}s: "
              f"{type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
