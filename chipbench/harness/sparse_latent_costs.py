"""Operations and bytes that decode attention over LATENT rows needs where
a learned indexer selects the rows (full layers) and where a window bounds
them (sliding layers), from shapes alone: the yardstick of the roofline
shares of the sparse latent decode path (indexer-score kernel, selection,
sparse latent attention kernel together) and of the window latent kernel.
Beside `flops.py` and `latent_moe_costs.py`, under the same rules: a
multiply-add counts as two operations, nothing computed twice counts twice,
and what is counted is the least an algorithm needs, not what a kernel
happens to move (a kernel that copies every PAGE holding a selected row
reads more than the selected rows; that is its loss, not the yardstick's).
The selection itself is counted as free: a k-th value needs no operation on
the MXU and no byte beyond the scores.
"""

from __future__ import annotations

from chipbench.harness import latent_moe_costs
# the indexer's scores cost what they cost over K/V rows: one key a cached
# token, scored by every index head; and a query attends its cached tokens
# and itself, at most `at_most` of them (`index_topk`, or the window, which
# counts the token itself)
from chipbench.harness.sparse_attention_costs import (  # noqa: F401
    indexer_score_cost as index_score_cost,
    keys_selected as rows_attended,
)


def bounded_latent_attention_cost(lengths, at_most: int, heads: int,
                                  key_width: int, value_width: int
                                  ) -> tuple[float, float]:
    """(operations, bytes) of ONE absorbed latent decode-attention call of
    ONE layer in which a slot's query attends `rows_attended(length,
    at_most)` rows: `latent_moe_costs.latent_attention_cost` (every
    attended row read ONCE for all heads and as key and value both, the
    absorbed queries read, the latent outputs written) over that many
    rows a slot."""
    return latent_moe_costs.latent_attention_cost(
        [rows_attended(n, at_most) - 1 for n in lengths], heads, key_width,
        value_width)
