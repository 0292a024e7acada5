"""Driver ``train_hybrid``: the ``train`` driver's job, checked against the
Granite 4.0-H hybrid's reference.

The set-up, the window's loop body, the device feed and the checks are
``drivers/train.py``'s. That module reads its reference and its operation
count from the module globals ``ref`` and ``flops``; this driver loads its
own copy of it and points those two at ``reference/granite_hybrid.py``
and ``flops_hybrid.py``, so the ``train`` driver itself is unchanged.
``_check_program`` also holds the program to the configuration's Mamba-2
keys, muP multipliers, position embedding and ``layer_types``.
"""

from __future__ import annotations

import os

from chipbench import flops_hybrid
from chipbench.harness import load_module
from chipbench.reference import granite_hybrid

_train = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py"),
                     "chipbench_driver_train_for_hybrid")
_train.ref = granite_hybrid
_train.flops = flops_hybrid

# the program's mixer names for the configuration's layer_types
_KINDS = {"mamba2": "mamba", "attn": "attention"}


class Job(_train.Job):
    def _check_program(self, mcfg, tcfg, template) -> None:
        super()._check_program(mcfg, tcfg, template)
        c, a, m = self.cfg, mcfg.attention, mcfg.mamba2
        full = list(mcfg.pattern) * mcfg.n_periods + list(mcfg.remainder)
        stated = {
            "mamba_n_heads": m.n_heads, "mamba_d_head": m.head_dim,
            "mamba_d_state": m.d_state, "mamba_n_groups": m.n_groups,
            "mamba_d_conv": m.d_conv, "mamba_expand": m.expand,
            "mamba_chunk_size": m.chunk,
            "embedding_multiplier": mcfg.embedding_multiplier,
            "residual_multiplier": mcfg.residual_multiplier,
            "logits_scaling": mcfg.logits_scaling,
            "attention_multiplier": a.scale,
            "position_embedding_type": "rope" if a.rope else "nope",
            "shared_intermediate_size": mcfg.d_ff,
            "num_local_experts": 0 if mcfg.moe is None else mcfg.moe.num_experts,
            "layer_types": [_KINDS.get(e.split(":")[0], e) for e in full],
        }
        bad = {k: (c[k], v) for k, v in stated.items() if c[k] != v}
        if bad:
            raise ValueError(f"the program departs from the configuration: {bad}")
