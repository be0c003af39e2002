"""moe_expert_decode_roofline (%): the experts' grouped products' share
of their roofline in the decode program.  Device time: the decode
program's operations under the scope ``moe/experts`` (the grouped
products, the gate between them), over the device events inside
``gen/decode`` regions.  Least time, a step: the touched experts'
weights (``moe_experts_touched`` x 12.4 MB) over the chip's HBM
bandwidth, or the routed operations (``moe_assignments`` pairs a layer x
gate, up and down) over its bf16 peak, whichever is larger
(``scope_ops.experts_roofline``)."""
from benchmark import scope_ops


def read(r):
    return scope_ops.experts_roofline(r, scope_ops.DECODE,
                                      "moe_expert_decode_roofline")
