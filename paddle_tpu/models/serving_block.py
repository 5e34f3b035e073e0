"""The seam `ServingEngine` steps a model that brings its own block
through (`models/afmoe.py`, `models/olmo_hybrid.py`): the architecture,
the weight tree (an argument of the jitted step) and the functions of
`(arch, weights, ...)` that the model's eager forward calls too."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ServingBlock:
    arch: object            # frozen description: `layers`, `layer_kinds`,
    #                          `num_heads`, `num_kv_heads`, `head_dim`, ...
    weights: dict
    embed: object           # (arch, weights, token_ids) -> h [T, D]
    layer: object           # (arch, li, layer weights, h, positions,
    #                          valid, attend[, recur]) -> (h, stats or
    #                          None); `recur` only where the architecture
    #                          has "linear" layers
    head: object            # (arch, weights, h rows) -> logits
    stat_names: tuple = ()  # flight-record names of the step's counters
    fold_stats: object = None   # (int32[len(stat_names)], a layer's
    #                              stats) -> int32[len(stat_names)]
