"""Chip benchmark of the serving path: one cell, one run, one JSON line.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.  Everything a cell needs is data found by name: the
cell in ``cells/<cell>.json``, its model configuration and deployment
(the engine's options) in ``configs/<config>.json``, the family that
configuration's ``reference`` names in ``archs/<reference>.py`` (program
config, weight tree, counts) and ``references/<reference>.py`` (plain
forward), its traffic mix in ``traffic/<mix>.json``, each per-layer
metric's reader in ``metrics/<metric>.py``, and the device peaks in
``peaks.json``.
"""
