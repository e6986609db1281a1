"""CLI entry point: `python -m elasticsearch_tpu <command>`.

Reference analogs (SURVEY.md §1 L10): distribution/tools/server-cli
(ServerCli → Elasticsearch.main), elasticsearch-plugin, and the
BootstrapChecks that gate startup.
"""

from __future__ import annotations

import argparse
import json
import sys

ES_VERSION = "8.x-tpu"


def cmd_serve(argv) -> int:
    from .rest import server

    server.main(list(argv))
    return 0


def cmd_version(_argv) -> int:
    import jax

    print(
        json.dumps(
            {
                "version": ES_VERSION,
                "distribution": "elasticsearch-tpu",
                "jax": jax.__version__,
            }
        )
    )
    return 0


def cmd_check(_argv) -> int:
    """Bootstrap checks (BootstrapChecks analog): device availability,
    kernel smoke, HBM budget sanity. Names the platform, device kind
    and device count it ran on — a pass on the CPU is a pass on the CPU."""
    failures = []
    device = {"platform": None, "kind": None, "count": 0}
    import numpy as np

    try:
        import jax

        devices = jax.devices()
        print(f"devices: {[str(d) for d in devices]}", file=sys.stderr)
        if not devices:
            failures.append("no JAX devices available")
        else:
            device = {
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
            }
            import jax.numpy as jnp

            out = jnp.sum(jnp.asarray(np.arange(8))).item()
            if out != 28:
                failures.append(f"device smoke kernel wrong result: {out}")
    except Exception as e:
        failures.append(f"jax initialization failed: {e}")
    from .common.memory import hbm_ledger

    if hbm_ledger.budget <= 0:
        failures.append("HBM budget is not positive")
    print(
        json.dumps(
            {
                "checks_passed": not failures,
                "failures": failures,
                "device": device,
                "hbm_budget_bytes": hbm_ledger.budget,
            }
        )
    )
    return 1 if failures else 0


def cmd_plugin(argv) -> int:
    from .plugins import plugins_service

    ap = argparse.ArgumentParser(prog="elasticsearch-tpu plugin")
    ap.add_argument("action", choices=["list", "load"])
    ap.add_argument("spec", nargs="?", help="module.path:ClassName for load")
    args = ap.parse_args(argv)
    try:
        if args.action == "load":
            if not args.spec:
                print("plugin load requires a spec", file=sys.stderr)
                return 2
            plugins_service.load_spec(args.spec)
        # load_spec/load_env are idempotent per spec, so a spec that is
        # also in ES_TPU_PLUGINS installs once
        plugins_service.load_env()
    except (ValueError, TypeError, ImportError, AttributeError) as e:
        print(f"plugin error: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"plugins": plugins_service.info()}))
    return 0


COMMANDS = {
    "serve": cmd_serve,
    "version": cmd_version,
    "check": cmd_check,
    "plugin": cmd_plugin,
}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(
            "usage: python -m elasticsearch_tpu "
            f"{{{'|'.join(COMMANDS)}}} [args]\n\n"
            "  serve    start the REST server (see --help for node flags)\n"
            "  version  print version info\n"
            "  check    run bootstrap checks (device, kernels, HBM)\n"
            "  plugin   list/load plugins",
        )
        return 0 if argv else 2
    cmd = COMMANDS.get(argv[0])
    if cmd is None:
        print(f"unknown command [{argv[0]}]", file=sys.stderr)
        return 2
    return cmd(argv[1:])


if __name__ == "__main__":
    raise SystemExit(main())
