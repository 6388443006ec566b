"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the continuous-batching engine (serve/engine.py) over the smoke
config, or with ``--full`` over the published widths, with seeded random
weights held in the compute dtype and synthetic requests.  Production
serving shapes (prefill_32k / decode_32k / long_500k) are lowered by
launch/dryrun.py.
"""

from __future__ import annotations

import argparse
from typing import List, Tuple

import jax
import numpy as np

from repro.configs.base import RunConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.runtime import compile_cache
from repro.serve.engine import Request, ServingEngine, init_serving_params


def main(argv=None) -> Tuple[ServingEngine, List[Request]]:
    """Serve synthetic requests; returns the engine and finished
    requests."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="published widths instead of the reduced "
                         "smoke config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV page tokens (default: RunConfig.kv_page_size "
                         "clamped to the context)")
    ap.add_argument("--hbm-frac", type=float, default=None,
                    help="fraction of KV pages resident in the HBM tier "
                         "(default: RunConfig.hbm_kv_budget_frac); the "
                         "rest demotes to the host-DRAM pool")
    ap.add_argument("--ttl-steps", type=int, default=None,
                    help="per-request residency bound in engine steps; "
                         "a request that has not finished within it is "
                         "dropped (pages freed, counted in stats) "
                         "instead of spinning its slot forever")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="token id that ends a request early")
    args = ap.parse_args(argv)

    compile_cache.configure()
    cfg = get_config(args.arch, smoke=args.smoke)
    rc = RunConfig(remat="none")
    print(f"[serve] arch={cfg.name} params={cfg.param_count():,} "
          f"device={jax.devices()[0].device_kind}")
    params = init_serving_params(cfg, rc, args.seed)
    engine = ServingEngine(cfg, rc, params, batch_slots=args.slots,
                           max_seq=args.prompt_len + args.max_new + 8,
                           page_size=args.page_size,
                           hbm_frac=args.hbm_frac,
                           eos_id=args.eos_id,
                           request_ttl_steps=args.ttl_steps)
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        shape = ((args.prompt_len, cfg.n_codebooks)
                 if cfg.family == "audio" else (args.prompt_len,))
        prompt = rng.integers(0, cfg.vocab_size, size=shape, dtype=np.int32)
        engine.submit(Request(rid, prompt, max_new_tokens=args.max_new))
    done = engine.run()
    for req in done:
        print(f"[serve] req {req.req_id}: {len(req.out_tokens)} tokens "
              f"{req.out_tokens[:8]}...")
    pg = engine.pages
    st = engine.stats
    print(f"[serve] {len(done)}/{args.requests} done in {engine.steps} "
          f"engine steps; page stats: {pg.stats}")
    if st["dropped"]:
        print(f"[serve] dropped {st['dropped']} request(s) "
              f"{st['dropped_ids']} (TTL/step-budget)")
    print(f"[serve] KV tiers: HBM {pg.hbm.n_pages - pg.hbm.n_free}/"
          f"{pg.hbm.n_pages} pages in use, host "
          f"{pg.host.n_pages - pg.host.n_free}/{pg.host.n_pages} — "
          f"page size {pg.page_size} tokens")
    return engine, done


if __name__ == "__main__":
    main()
