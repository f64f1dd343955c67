"""The contract of a family whose cache holds per-slot state with no
length axis (a recurrent state, a convolution window) beside keys and
values: Qwen3-Next's Gated DeltaNet, Jamba's Mamba.

Such a family serves from the dense slot layout of ``ml/generate.py``
alone. Its ``prefill_into`` computes the state from zero, so a slot's
reuse is its reset, and the padded positions of the one-row prefill ladder
leave state and window exactly as the last real token did. Its
``decode_step`` moves every row's state, an idle row's too (the next
occupant's prefill overwrites it). Its cache names the state ``state`` and
the window ``conv``; ``Generator.pool_stats()`` reports their bytes beside
the keys' and values'. What ``Generator`` refuses for it at construction,
each with what it would take, is ``UNSUPPORTED``: one table for every such
family.
"""

from __future__ import annotations

__all__ = ["UNSUPPORTED"]

UNSUPPORTED = {
    "page_size": "the recurrent state has no pages: the paged layout, the "
                 "prefix cache, kv_offload and kv_transport need snapshots "
                 "of it at page boundaries",
    "sp": "sequence-parallel prefill would have to hand the recurrent "
          "state from shard to shard",
    "spec_k": "a rejected draft token has already changed the recurrent "
              "state; speculation needs a checkpoint of it per window",
    "kv_bits": "the attention layers' cache is served in the model's dtype "
               "only (no int8/int4 planes)",
    "prefill_chunk": "a prompt's segments would have to carry the "
                     "recurrent state from one to the next",
    "mesh": "the family has no sharding rules (shard_cache and "
            "tensor-parallel replicas need them, and an expert exchange "
            "where it routes)",
}
