"""One front door for every launcher: ``python -m repro <subcommand>``.

    python -m repro tune                    # sweep + fit all tuner families
    python -m repro evaluate --smoke        # paper evaluation protocol
    python -m repro serve-estimator --demo  # online serving tier
    python -m repro serve-worker --listen 0.0.0.0:7071 --register /shared/reg.jsonl
    python -m repro dryrun --all            # multi-pod lowering dry-run
    python -m repro mesh                    # inspect mesh construction
    python -m repro train --preset small    # training driver
    python -m repro serve --preset small    # batched decode driver

Each subcommand resolves to the matching ``repro.launch.<module>`` main;
the old ``python -m repro.launch.<module>`` spellings keep working as
thin shims that point here.  Dispatch rewrites ``sys.argv`` *before*
importing the target module, because several launchers peek at argv at
import time (``--host-devices`` must set ``XLA_FLAGS`` before jax
initializes) and parse ``sys.argv`` in ``main()``.
"""
from __future__ import annotations

import importlib
import os
import sys
from pathlib import Path

#: where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed path in the checkout, so the next run finds them again
COMPILE_CACHE = Path(__file__).resolve().parents[3] / "artifacts" / "jax_cache"

# subcommand -> (module, one-line help).  Underscored spellings are
# accepted as aliases of the dashed ones.
COMMANDS = {
    "tune": ("repro.launch.tune",
             "sweep all tuner families into one LogStore and fit"),
    "evaluate": ("repro.launch.evaluate",
                 "paper evaluation protocol (speedup vs default blocks)"),
    "serve-estimator": ("repro.launch.serve_estimator",
                        "online serving tier: warm, serve a trace, report"),
    "serve-worker": ("repro.launch.serve_worker",
                     "standalone socket shard worker (+ lease registry)"),
    "dryrun": ("repro.launch.dryrun",
               "multi-pod lowering dry-run (sets XLA_FLAGS first)"),
    "mesh": ("repro.launch.mesh",
             "construct and describe a device mesh"),
    "train": ("repro.launch.train",
              "end-to-end training driver with fault tolerance"),
    "serve": ("repro.launch.serve",
              "batched prefill+decode serving driver"),
}

_ALIASES = {name.replace("-", "_"): name for name in COMMANDS
            if "-" in name}


def _usage(out=None) -> None:
    out = out or sys.stdout
    print("usage: python -m repro <subcommand> [args...]\n", file=out)
    print("subcommands:", file=out)
    for name, (_mod, desc) in COMMANDS.items():
        print(f"  {name:<16} {desc}", file=out)
    print("\n`python -m repro <subcommand> --help` shows that "
          "launcher's flags.", file=out)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache is ``COMPILE_CACHE``.
    Call it from an entry point, before the first compile.

    The cache key includes the programs' metadata: by default JAX leaves it
    out, and a program loaded from the cache then carries the name stacks
    (the step's named scopes, ``repro.runtime.obs.program_text``) of
    whichever version of the code compiled it first."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    return str(COMPILE_CACHE)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        _usage()
        return 0
    cmd = _ALIASES.get(argv[0], argv[0])
    if cmd not in COMMANDS:
        print(f"python -m repro: unknown subcommand {argv[0]!r}",
              file=sys.stderr)
        _usage(sys.stderr)
        return 2
    module, _desc = COMMANDS[cmd]
    # the target must see exactly its own args — both the launchers that
    # argparse sys.argv[1:] and the ones that peek argv at import time
    sys.argv = [f"python -m repro {cmd}"] + argv[1:]
    use_compile_cache()
    mod = importlib.import_module(module)
    mod.main()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
