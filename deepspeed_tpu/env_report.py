"""ds_report — environment / op compatibility report
(reference deepspeed/env_report.py:23-50: prints the op install/compat matrix
and torch/cuda versions; here jax/libtpu and the TPU op registry).
"""

GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
SUCCESS = GREEN + "[YES]" + END
WARNING = YELLOW + "[WARNING]" + END
FAIL = RED + "[NO]" + END
OKAY = GREEN + "[OKAY]" + END


def op_report():
    from deepspeed_tpu.op_builder import ALL_OPS
    max_dots = 23
    print("-" * 64)
    print("DeepSpeed-TPU ops report")
    print("-" * 64)
    print("op name" + "." * (max_dots - len("op name")) + "compatible")
    print("-" * 64)
    rows = []
    for op_name, builder_cls in ALL_OPS.items():
        builder = builder_cls()
        compat = builder.is_compatible()
        status = OKAY if compat else FAIL
        kind = "pallas" if not builder.sources() else "c++"
        line = "{} [{}]{}{}".format(
            op_name, kind, "." * max(max_dots - len(op_name) - len(kind) - 3,
                                     1), status)
        print(line)
        rows.append((op_name, kind, compat))
    print("-" * 64)
    return rows


def version_report():
    import jax
    import jaxlib
    print("DeepSpeed-TPU general environment info:")
    try:
        import deepspeed_tpu
        print("deepspeed install path ...", deepspeed_tpu.__path__)
        print("deepspeed info ...........", deepspeed_tpu.__version__)
    except Exception:
        pass
    print("jax version ..............", jax.__version__)
    print("jaxlib version ...........", jaxlib.__version__)
    # Asked in this process: the chip belongs to one process at a time, so
    # a child started from here could not reach it anyway.
    devices = jax.devices()
    print("jax backend ..............", jax.default_backend())
    print("device count .............", len(devices))
    print("device kind ..............", devices[0].device_kind)
    try:
        import flax
        print("flax version .............", flax.__version__)
    except ImportError:
        print("flax version .............", "not installed")


def tuning_report():
    """Kernel-tuning knobs and table status (the reference's analogue is
    the op compat matrix; these govern which TPU kernel paths run)."""
    import json
    import os
    print("kernel tuning:")
    print("flash backward path ......",
          os.environ.get("DS_TPU_FLASH_BWD", "auto"))
    print("xe head impl .............",
          os.environ.get("DS_TPU_XE_HEAD", "eager"))
    print("online autotune ..........",
          os.environ.get("DS_TPU_AUTOTUNE", "0"))
    try:
        from deepspeed_tpu.ops import autotuner
        with open(autotuner._BUNDLED_PATH) as f:
            n = len(json.load(f))
        print("autotune table entries ...", n)
    except Exception:
        print("autotune table entries ...", "none")


def main():
    op_report()
    version_report()
    tuning_report()


def cli_main():
    main()


if __name__ == "__main__":
    main()
