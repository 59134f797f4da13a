"""``python -m dynamo_tpu.cli`` — the unified entrypoint.

Reference parity: launch/dynamo-run/src/opt.rs (one binary fronting every
input/output pairing) plus the service launchers under components/. Service
subcommands re-exec the dedicated module mains so flags stay in one place.
"""

from __future__ import annotations

import argparse
import asyncio
import sys

from dynamo_tpu import config
from dynamo_tpu.cli.run import (
    add_drain_args,
    add_observe_args,
    add_run_args,
    main_drain,
    main_observe,
    main_run,
)

# One source of truth for service kinds (deploy specs use the same table);
# the CLI adds hyphen aliases and the deploy controller itself.
from dynamo_tpu.deploy.spec import KIND_MODULES

_SERVICES = {
    **KIND_MODULES,
    "global-router": KIND_MODULES["global_router"],
    "deploy": "dynamo_tpu.deploy",
}


def cmd_env(markdown: bool = False) -> None:
    """Print the DYN_* registry (config.py advertises this command)."""
    import os

    if markdown:
        # The docs/design_docs/config_knobs.md body; a tier-1 test pins
        # the checked-in file to this output.
        print(config.render_markdown())
        return
    rows = sorted(config.registry().items())
    width = max(len(n) for n, _ in rows)
    for name, var in rows:
        current = os.environ.get(name)
        state = f" [set: {current}]" if current is not None else ""
        print(f"{name:<{width}}  default={var.default!r}{state}")
        if var.doc:
            print(f"{'':<{width}}  {var.doc}")


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in _SERVICES:
        # Delegate: `dynamo_tpu.cli worker --model tiny` ≡
        # `python -m dynamo_tpu.worker --model tiny`.
        module = _SERVICES[argv[0]]
        sys.argv = [f"{module}"] + argv[1:]
        import runpy

        runpy.run_module(module, run_name="__main__")
        return

    parser = argparse.ArgumentParser(
        "dynamo-tpu",
        description="unified CLI: run engines locally, inspect config, "
        f"or launch services ({', '.join(_SERVICES)})",
    )
    sub = parser.add_subparsers(dest="command", required=True)
    run_p = sub.add_parser("run", help="drive a local engine (text/stdin/batch/http)")
    add_run_args(run_p)
    observe_p = sub.add_parser(
        "observe",
        help="snapshot a running worker's device plane "
        "(/debug/memory /debug/compiles /debug/flight); sub-views: "
        "trajectory, kvcache",
    )
    add_observe_args(observe_p)
    drain_p = sub.add_parser(
        "drain",
        help="live-handoff drain a running worker (POST /drain; in-flight "
        "decodes migrate to peers with zero re-prefill)",
    )
    add_drain_args(drain_p)
    # Lazy import: lint is jax-free and must stay that way (it runs on
    # boxes where the serving deps don't), so it can't ride cli.run's
    # imports.
    from dynamo_tpu.analysis.cli import add_lint_args

    lint_p = sub.add_parser(
        "lint",
        help="run the dynlint static-analysis passes over the package "
        "(exit 1 on non-baselined findings)",
    )
    add_lint_args(lint_p)
    env_p = sub.add_parser(
        "env", help="print the environment-variable registry"
    )
    env_p.add_argument(
        "--markdown", action="store_true",
        help="emit the docs/design_docs/config_knobs.md reference table",
    )
    args = parser.parse_args(argv)

    if args.command == "env":
        cmd_env(markdown=args.markdown)
    elif args.command == "run":
        asyncio.run(main_run(args))
    elif args.command == "observe":
        asyncio.run(main_observe(args))
    elif args.command == "drain":
        asyncio.run(main_drain(args))
    elif args.command == "lint":
        from dynamo_tpu.analysis.cli import main_lint

        raise SystemExit(main_lint(args))


if __name__ == "__main__":
    main()
