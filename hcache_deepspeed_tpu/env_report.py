"""Environment / capability report.

Reference analog: ``bin/ds_report`` → ``deepspeed/env_report.py`` — op
compatibility table + version/platform summary. Here the "ops" are the
Pallas kernel registry plus platform capabilities.
"""

import sys


GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def collect_report():
    import jax

    from .platform import get_platform
    from . import ops as ops_pkg
    from .version import __version__

    plat = get_platform()
    report = {
        "version": __version__,
        "jax_version": jax.__version__,
        "platform": type(plat).__name__,
        "device_kind": plat.device_kind(),
        "device_count": plat.device_count(),
        "process_count": plat.process_count(),
        "supports_pallas": plat.supports_pallas(),
        "supports_host_offload": plat.supports_host_offload(),
        "peak_bf16_tflops": None if plat.name == "cpu"
        else plat.peak_tflops("bfloat16"),
        "op_table": ops_pkg.op_report(),
    }
    return report


def main(argv=None):
    report = collect_report()
    print("-" * 60)
    print("hcache_deepspeed_tpu environment report (hds_report)")
    print("-" * 60)
    for key in ("version", "jax_version", "platform", "device_kind",
                "device_count", "process_count", "peak_bf16_tflops"):
        print(f"{key:.<32} {report[key]}")
    print("-" * 60)
    print("capability / op compatibility")
    print("-" * 60)
    for cap in ("supports_pallas", "supports_host_offload"):
        print(f"{cap:.<32} {GREEN_OK if report[cap] else RED_NO}")
    print(report["op_table"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
