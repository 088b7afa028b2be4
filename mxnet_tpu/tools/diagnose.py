"""Environment diagnosis tool.

Reference: tools/diagnose.py — prints everything a bug report needs
(platform, python, dependency versions, hardware visibility, build
features). TPU-native additions: JAX backend/devices, native runtime
library status, and the MXNET_* env-knob audit.

Run: ``python -m mxnet_tpu.tools.diagnose``
"""
from __future__ import annotations

import importlib
import os
import platform
import sys
import time


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_pip():
    print("------------Pip Info-----------")
    try:
        import pip

        print("Version      :", pip.__version__)
        print("Directory    :", os.path.dirname(pip.__file__))
    except ImportError:
        print("No corresponding pip install for current python.")


def check_deps():
    print("----------Deps Info----------")
    for mod in ("jax", "jaxlib", "flax", "optax", "numpy", "chex"):
        try:
            m = importlib.import_module(mod)
            print(f"{mod:<12} : {getattr(m, '__version__', 'unknown')}")
        except ImportError:
            print(f"{mod:<12} : not installed")


def check_mxnet():
    print("----------MXNet-TPU Info-----------")
    import mxnet_tpu as mx

    print("Version      :", mx.__version__)
    print("Directory    :", os.path.dirname(mx.__file__))
    from mxnet_tpu import runtime

    feats = runtime.Features()
    enabled = [name for name in feats.keys() if feats.is_enabled(name)]
    print("Features     :", ", ".join(enabled))
    from mxnet_tpu import _native

    print("Native libs  : recordio=%s engine=%s textio=%s" % (
        "ok" if _native.lib is not None else "missing",
        "ok" if _native.englib is not None else "missing",
        "ok" if _native.textlib is not None else "missing"))


def check_hardware():
    print("----------Hardware Info----------")
    print("Machine      :", platform.machine())
    print("Processor    :", platform.processor() or "unknown")
    try:
        with open("/proc/cpuinfo") as f:
            models = {ln.split(":", 1)[1].strip() for ln in f
                      if ln.startswith("model name")}
        for m in sorted(models):
            print("CPU model    :", m)
    except OSError:
        pass
    print("----------Accelerator Info----------")
    try:
        import jax

        t0 = time.time()
        devs = jax.devices()
        dt = time.time() - t0
        print(f"Backend      : {devs[0].platform if devs else 'none'} "
              f"(init {dt:.1f}s)")
        for d in devs:
            print(f"Device       : {d.id} {d.device_kind}")
        print("Process count:", jax.process_count())
        return devs[0].platform if devs else None
    except Exception as e:  # backend init failed: report, don't crash
        print("Accelerator  : unavailable:", str(e)[:200])
        return None


def check_environment():
    print("----------Environment----------")
    for k, v in sorted(os.environ.items()):
        if k.startswith(("MXNET_", "JAX_", "XLA_", "LD_", "OMP_")):
            print(f"{k}={v}")
    from mxnet_tpu import env

    env.check()  # warns on set-but-unknown MXNET_* vars


def main(argv=None):
    """``--require-tpu``: exit non-zero unless jax's backend is a TPU
    (the report alone never fails — it is meant to run anywhere)."""
    argv = sys.argv[1:] if argv is None else argv
    check_python()
    check_pip()
    check_deps()
    check_mxnet()
    backend = check_hardware()
    check_environment()
    if "--require-tpu" in argv and backend != "tpu":
        print(f"--require-tpu: backend is {backend!r}, not 'tpu'",
              file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
