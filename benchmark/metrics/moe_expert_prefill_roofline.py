"""moe_expert_prefill_roofline (%): the experts' grouped products' share
of their roofline in the prefill programs: as
``moe_expert_decode_roofline``, over the device events inside
``gen/prefill/call`` regions, each region's events held against the text
of the program it called.  Operations by the call's ``moe_assignments``
(its VALID tokens x 8: a padded position is routed nowhere and earns
nothing), bytes by its ``moe_experts_touched``."""
from benchmark import scope_ops


def read(r):
    return scope_ops.experts_roofline(r, scope_ops.PREFILL,
                                      "moe_expert_prefill_roofline")
