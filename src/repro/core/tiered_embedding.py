"""Tiered embedding table: the paper's OS-exposed mechanism, for LM vocabs.

Token frequencies are Zipfian, exactly the row-popularity skew TL-DRAM
exploits: a small near tier of hot vocabulary rows serves most lookups via
the VMEM-resident fast path (`kernels.tiered_gather`), while the bulk table
stays in HBM (far tier).  The shared vectorized engine
(`repro.tier.jax_engine`) decides membership from decayed token activation
counts under any of the four paper policies (BBC by default; STATIC preloads
from a profiled count vector); `refresh` re-copies hot rows after parameter
updates (training) — the IST analogue.

Applicability: enabled for vocab >= 32k archs; for tiny vocabularies
(musicgen's 2048 codes) the whole table fits the near tier and the mechanism
degenerates (docs/design.md §Arch-applicability).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.tier import TierCosts, ema_update
from repro.tier.jax_engine import (apply_promotions, plan_promotions,
                                   preload_static)
from repro.kernels import interpret_mode
from repro.kernels.tiered_gather import tiered_gather

DEFAULT_COSTS = TierCosts(near_cost=1.0, far_cost=4.0, migrate_cost=6.0,
                          hysteresis=1.5, min_score=2.0, decay=0.9)


@dataclass
class TieredEmbeddingConfig:
    near_rows: int = 1024
    max_promotions: int = 64
    policy: str = "BBC"           # SC | WMC | BBC | STATIC
    costs: TierCosts = DEFAULT_COSTS


def init_state(table: jax.Array, cfg: TieredEmbeddingConfig) -> dict:
    V, D = table.shape
    C = cfg.near_rows
    return {
        "near_table": jnp.zeros((C, D), table.dtype),
        "slot_of_token": -jnp.ones((V,), jnp.int32),
        "token_of_slot": -jnp.ones((C,), jnp.int32),
        "scores": jnp.zeros((V,), jnp.float32),
        # SC/WMC LRU stamps: batch index of each token's last occurrence.
        "last_use": jnp.zeros((V,), jnp.float32),
        "step": jnp.zeros((), jnp.int32),
        "migrations": jnp.zeros((), jnp.int32),
    }


def lookup(table: jax.Array, state: dict, tokens: jax.Array,
           interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Two-tier lookup.  tokens: (...,) int32.  Returns (embeddings, hit_mask).

    Near hits resolve from the VMEM-pinned near table inside the Pallas
    kernel; misses take the HBM gather (far path).
    """
    if interpret is None:
        interpret = interpret_mode()
    shape = tokens.shape
    flat = tokens.reshape(-1)
    slots = state["slot_of_token"][flat]
    far_values = jnp.take(table, flat, axis=0)
    out = tiered_gather(state["near_table"], slots, far_values,
                        interpret=interpret)
    hits = slots >= 0
    return out.reshape(*shape, table.shape[1]), hits.reshape(shape)


def record_and_migrate(table: jax.Array, state: dict, tokens: jax.Array,
                       cfg: TieredEmbeddingConfig, idle=True) -> dict:
    """EMA-update token scores with this batch's counts, then run
    ``cfg.policy`` and copy newly-promoted rows into the near tier (pure
    on-device copies).  ``idle`` is the WMC gate (SC/BBC ignore it)."""
    if cfg.policy.upper() == "STATIC":
        return state   # OS-exposed mechanism: no runtime migration, and no
                       # point paying the counting pass for dead state
    state = dict(state)
    V = table.shape[0]
    counts = jnp.zeros((V,), jnp.float32).at[tokens.reshape(-1)].add(1.0)
    state["scores"] = ema_update(state["scores"], counts, cfg.costs)
    state["last_use"] = jnp.where(counts > 0,
                                  state["step"].astype(jnp.float32),
                                  state["last_use"])
    state["step"] = state["step"] + 1

    # SC/WMC cache what was *accessed this batch*; BBC keeps its sustained-
    # reuse eligibility over the full EMA score population.
    accessed = (counts > 0) if cfg.policy.upper() in ("SC", "WMC") else None
    rows, slots, valid = plan_promotions(
        state["scores"], state["slot_of_token"], state["token_of_slot"],
        cfg.costs, cfg.max_promotions, policy=cfg.policy,
        last_use=state["last_use"], accessed=accessed, idle=idle)
    state["slot_of_token"], state["token_of_slot"] = apply_promotions(
        state["slot_of_token"], state["token_of_slot"], rows, slots, valid)

    # IST: copy promoted rows (scatter into the near table, no collectives)
    safe_rows = jnp.where(valid, rows, 0)
    new_rows = jnp.take(table, safe_rows, axis=0)
    dst = jnp.where(valid, slots, state["near_table"].shape[0])
    state["near_table"] = state["near_table"].at[dst].set(new_rows,
                                                          mode="drop")
    state["migrations"] = state["migrations"] + valid.sum().astype(jnp.int32)
    return state


def preload_static_embedding(table: jax.Array, state: dict,
                             profile_counts: jax.Array,
                             cfg: TieredEmbeddingConfig) -> dict:
    """OS-exposed static placement: pin the profile's hottest tokens in the
    near tier at t=0 (serve with ``policy="STATIC"``, no runtime migration).

    profile_counts: (V,) profiled token frequencies."""
    state = dict(state)
    C = state["token_of_slot"].shape[0]
    state["slot_of_token"], state["token_of_slot"] = preload_static(
        profile_counts.astype(jnp.float32), C)
    return refresh(table, state)


def refresh(table: jax.Array, state: dict) -> dict:
    """Re-copy every cached row from the (possibly updated) master table —
    call after optimizer steps touching the embedding."""
    state = dict(state)
    C = state["token_of_slot"].shape[0]
    toks = state["token_of_slot"]
    rows = jnp.take(table, jnp.maximum(toks, 0), axis=0)
    live = (toks >= 0)[:, None]
    state["near_table"] = jnp.where(live, rows.astype(state["near_table"].dtype),
                                    state["near_table"])
    return state


def hit_rate(state: dict, tokens: jax.Array) -> jax.Array:
    return (state["slot_of_token"][tokens.reshape(-1)] >= 0).mean()
