"""CLI: validate or inspect the tuning cache, or sweep a cell in-process.

    python -m repro.autotune --validate            # ci.sh schema gate
    python -m repro.autotune --show                # print resolved entries
    python -m repro.autotune --sweep 16,16,32      # sweep on this host's devices

``--validate`` exits non-zero on any schema problem (a MISSING cache file
is valid — the cache is optional by design), which is what ``ci.sh`` runs.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.autotune.cache import TuningCache, default_cache_path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.autotune")
    ap.add_argument("--cache", default=None, help=f"cache path (default {default_cache_path()})")
    ap.add_argument("--validate", action="store_true", help="schema-check the cache; exit 1 on problems")
    ap.add_argument("--show", action="store_true", help="dump the cache cells as JSON")
    ap.add_argument("--sweep", default=None, metavar="N1,N2,N3",
                    help="sweep one grid cell on this process's devices (1xD mesh)")
    ap.add_argument("--beta", type=float, default=1e-2)
    args = ap.parse_args(argv)

    cache = TuningCache(args.cache)
    if args.validate:
        problems = cache.validate()
        for p in problems:
            print(f"autotune cache INVALID: {p}", file=sys.stderr)
        if not problems:
            print(f"autotune cache OK: {cache.path}")
        return 1 if problems else 0
    if args.show:
        print(json.dumps(cache.load(), indent=2, sort_keys=True))
        return 0
    if args.sweep:
        import jax

        from repro.autotune.search import sweep_cell
        from repro.core.grid import make_grid
        from repro.launch.mesh import make_mesh

        shape = tuple(int(x) for x in args.sweep.split(","))
        mesh = make_mesh((1, jax.device_count()), ("data", "model"))
        rec = sweep_cell(make_grid(shape), mesh, beta=args.beta, cache=cache)
        print(json.dumps(rec, indent=2))
        return 0
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
