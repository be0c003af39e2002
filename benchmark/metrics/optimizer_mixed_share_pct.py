"""optimizer_mixed_share_pct (%): device seconds of the fusions that
hold operations both under the program's ``train/optimizer`` scope and
outside it (XLA writes a gradient straight into its stacked bucket: the
backward's last operation and the optimizer's packing in one fusion) /
device-busy seconds.  The trace cannot split such a fusion's time, so
``optimizer_device_share_pct`` counts none of it: the optimizer's share
lies between that reading and that reading plus this one."""
from benchmark import program_spans


def read(r):
    return program_spans.device_share_pct(r, "train/optimizer", "mixed")
