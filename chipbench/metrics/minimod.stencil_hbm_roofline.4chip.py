"""The split stencil step's share of its HBM roofline on each of four
chips, by the yardstick of ``minimod.stencil_hbm_roofline`` and with its
reader: the least bytes the traced steps need on one chip (16 B per cell
of its share and step) at the HBM peak, over the device time of the
step's non-collective ops (the boundary, interior and combining passes),
averaged over the chips."""

from pathlib import Path

from chipbench.spec import load_module

read = load_module(Path(__file__).with_name("minimod.stencil_hbm_roofline.py"),
                   "metric_minimod.stencil_hbm_roofline").read
