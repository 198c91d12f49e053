"""Share of its roofline that a prefill chunk's retention reaches: the
least time the chip could take for the chunk's three products (inside the
chunk the masked `(Q K^T)^p` and `A V`, across chunks `phi(Q) S_0`, and the
state's update; `harness/retention_costs.py`, compute-bound), all layers,
over the device time a whole call of `programs.prefill` spends in the part
`attn.attend` (`harness/trace_scopes.py`): the chunk form is a Pallas
kernel for the two state-wide products and XLA's operations for the rest,
and the share is of ALL of it. Every chunk is counted as full; a prompt's
last chunk holds 1 to `chunk` real rows and the program computes its
padding like any row, so over prompts of ~18.5 chunks the share reads
under 3% (of the share) too high. Nothing to read where the program's
operations carry no such part or the configuration is no retention
model."""
from chipbench.harness import flops, retention_costs, trace_scopes


def read(run):
    cfg = run.cell.config
    if run.trace is None or run.peaks is None \
            or "retention_degree" not in cfg:
        return None
    spent_ms = trace_scopes.part_ms(run, "prefill", "attn.attend")
    if not spent_ms:
        return None
    least = flops.roofline_seconds(*retention_costs.chunk_cost(
        run.cell.shape["engine"]["prefill_chunk"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["retention_degree"]), run.peaks)[0]
    return 100.0 * least * cfg["num_hidden_layers"] / (spent_ms / 1e3)
