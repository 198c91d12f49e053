"""The repo's declared collective contracts, in ONE table.

Exact collective-permute pins are those of the installed jax's
`jax.shard_map` lowering (jax 0.9.0), which CSEs the rotation permutes
inside scan bodies: one row per program here.

The structural clauses (`forbid`/`require`) are lowering-independent and
are what actually sets each mode's performance class — a ring that
all-gathers the sequence is not a ring, whatever the permute count.
"""

from __future__ import annotations

from .program import CANONICAL_COLLECTIVES, CollectiveContract

__all__ = [
    "contract_for",
    "shard_map_contracts",
    "serving_program_contracts",
    "pod_program_contracts",
]


# program name -> exact pins + lowering-independent structure.
# Pins guard against silent rewrites (a doubled rotation, a CSE
# regression); structure guards against degeneration (gather-the-world).
_SHARD_MAP_TABLE: dict[str, dict] = {
    # one rotation = one permute per rotated buffer (K and V) in the scan
    # body
    "ring_attention.forward": dict(
        pins={"collective-permute": 2},
        forbid=("all-gather", "all-to-all"),
    ),
    # fwd K/V + bwd recompute + dK/dV return rings
    "ring_attention.backward": dict(
        pins={"collective-permute": 8},
        forbid=("all-gather",),
    ),
    # GPipe/1F1B: one fwd shift + one bwd shift in the loop bodies;
    # activations/params never gather across the stage axis, grads
    # all-reduce
    "pipeline.step": dict(
        pins={"collective-permute": 2},
        forbid=("all-gather", "all-to-all"),
        require=("all-reduce",),
    ),
    # Ulysses scatters heads with all-to-all; the CPU partitioner
    # decomposes one logical a2a into per-pair ops, so the count is
    # structural (>0), not pinned
    "ulysses.attention": dict(
        pins={},
        at_least={"all-to-all": 1},
        forbid=("all-gather", "collective-permute"),
    ),
}


def shard_map_contracts() -> dict[str, CollectiveContract]:
    """Every shard_map program contract."""
    out: dict[str, CollectiveContract] = {}
    for name, row in _SHARD_MAP_TABLE.items():
        out[name] = CollectiveContract(
            name=name,
            exact=row.get("pins", {}),
            at_least=row.get("at_least", {}),
            require=row.get("require", ()),
            forbid=row.get("forbid", ()),
        )
    return out


def contract_for(name: str) -> CollectiveContract:
    """Resolve one named contract."""
    contracts = shard_map_contracts()
    if name not in contracts:
        raise KeyError(
            f"no contract named {name!r}; known: {sorted(contracts)}")
    return contracts[name]


def serving_program_contracts(
    paged_kernel: bool = False,
    speculative: bool = False,
) -> dict[str, CollectiveContract]:
    """Default contracts for a SINGLE-DEVICE serving engine's three
    programs: admit/prefill/decode must carry NO collectives — one
    appearing means a sharding leak (params accidentally mesh-placed) or
    an explicit psum snuck into a model forward. The paged-KV cache's
    page-table gathers/scatters (serving/cache.py) are plain data
    movement — `gather`/`scatter` HLO, deliberately NOT in
    CANONICAL_COLLECTIVES — so the exhaustive no-collectives clause
    covers the paged programs unchanged.

    `paged_kernel=True` is the kernel-backed decode variant
    (`EngineConfig(paged_attention=True)`): the Pallas paged-attention
    custom call is a chip-local op — not a collective, not a host
    transfer — so the decode program keeps the SAME exhaustive
    no-collectives clause; the variant is named distinctly so a contract
    failure report says which decode flavor it audited.

    `speculative=True` is the draft-model speculative-decoding engine
    (`EngineConfig(speculative=...)`): the one-token decode is replaced
    by the `draft_prefill`/`draft`/`verify` trio — all still chip-local
    (the draft runs against its own dense slot cache, the verify is the
    same short-sequence paged forward prefill already is), so every
    program keeps the exhaustive no-collectives clause; they are named
    so a contract failure says which of the five programs it audited.

    "No collectives" is the single-device promise only: a mesh-sharded
    engine (`EngineConfig(mesh=...)`, serving/pod) MUST communicate, and
    its strict audit defaults to `pod_program_contracts()` below —
    which pins the tensor-parallel collectives instead of forbidding
    them. Engines with bespoke sharding pass their own contracts via
    `EngineConfig(contracts=...)`."""
    variant = {"decode": ".paged-kernel" if paged_kernel else ""}
    names = (("admit", "prefill", "draft_prefill", "draft", "verify")
             if speculative else ("admit", "prefill", "decode"))
    return {
        name: CollectiveContract(
            name=f"serving.{name}{variant.get(name, '')}",
            forbid=CANONICAL_COLLECTIVES,
            exhaustive=True,
        )
        for name in names
    }


def pod_program_contracts(
    num_layers: int | None = None,
    paged_kernel: bool = False,
) -> dict[str, CollectiveContract]:
    """Contracts for a tensor-parallel (mesh-sharded) serving engine's
    programs (`serving/pod` layer 1, audited against the COMPILED HLO —
    GSPMD inserts these collectives after lowering).

    - `prefill`/`decode` run the sharded family forward: every layer's
      row-parallel projections (attention out, MLP down) must reduce
      partial sums across the model axis, so the programs REQUIRE a
      reduction (all-reduce, or the reduce-scatter spelling some
      partitioners pick) and, when `num_layers` is known, at least one
      all-reduce per layer. The partitioner is free to add
      all-gathers/collective-permutes for resharding (their count varies
      with mesh width and XLA version — structural clauses, not pins),
      but an all-to-all would mean head/sequence re-scattering the
      serving layout never asks for: forbidden.
    - `admit` touches only per-slot scalars (lengths/keys/temps) that
      replicate: still NO collectives, exhaustively — a collective here
      means the slot state accidentally sharded.
    - `extract`/`install` (the page-shipping programs,
      serving/pod/transfer.py) gather/scatter pool pages (int8 pools:
      codes + scale blocks, shipped verbatim): chip-local when the pool
      is head-sharded, at most resharding movement when it is not (incl.
      the page-dim-sharded GQA fallback); an all-to-all or reduction
      would mean page *contents* are being recombined across chips,
      which the shipment design never does: forbidden.

    `paged_kernel=True` names the decode contract's kernel-backed
    variant with UNCHANGED clauses (a pallas custom call is chip-local —
    not a collective). Today a MESHED engine always resolves
    `paged_attention` to the dense path (the kernel is opaque to GSPMD),
    so this variant is reached only by a future shard_map-wrapped
    kernel; the pod layer composes with the kernel through its
    single-device decode workers, which audit under
    `serving_program_contracts(paged_kernel=True)`."""
    moving = dict(
        require=(("all-reduce", "reduce-scatter"),),
        forbid=("all-to-all",),
    )
    if num_layers:
        moving["at_least"] = {"all-reduce": int(num_layers)}
    decode_name = ("serving.pod.decode.paged-kernel" if paged_kernel
                   else "serving.pod.decode")
    return {
        "admit": CollectiveContract(
            name="serving.pod.admit", forbid=CANONICAL_COLLECTIVES,
            exhaustive=True),
        "prefill": CollectiveContract(name="serving.pod.prefill", **moving),
        "decode": CollectiveContract(name=decode_name, **moving),
        "extract": CollectiveContract(
            name="serving.pod.extract",
            forbid=("all-to-all", "all-reduce", "reduce-scatter")),
        "install": CollectiveContract(
            name="serving.pod.install",
            forbid=("all-to-all", "all-reduce", "reduce-scatter")),
    }
