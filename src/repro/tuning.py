"""Perf-iteration flags for the LM stack.

Each hillclimb is a named flag so baseline vs optimized lower from the
SAME code path; the dry-run runs twice and records both:

  REPRO_TUNING=mla_cache_rep,moe_ep,cp_decode python -m repro.launch.dryrun ...

  mla_cache_seq  H1: shard the MLA latent cache's SEQUENCE over `model`
                 (context parallelism) — scores stay local per shard and
                 only softmax partials + the (B,H,r) output psum, instead
                 of the baseline's per-layer (B,H,S) score psum.
  moe_ep         H2: shard_map expert-parallel MoE dispatch (argsort
                 bucketing per chip + psum combine) instead of the global
                 scatter GSPMD replicates.
  cp_decode      H3: sequence-parallel decode attention — partial softmax
                 (m, l, acc) psum over the KV shards instead of
                 all-gathering the cache (DEAL SPMM's "ship the small
                 partials" applied to attention).
"""
from __future__ import annotations

import os
from typing import Set


def flags() -> Set[str]:
    return set(filter(None, os.environ.get("REPRO_TUNING", "").split(",")))


def on(name: str) -> bool:
    return name in flags()
