"""Share (%) of its roofline that a MoE cell's decode executable reaches:
``decode_roofline``'s reading, whose floor comes from the family's counts
(``bench/flops_moe.py``: per step the dense weights, the held experts a
token is expected to pick, the valid cache, window layers at most
``window`` slots)."""
import os

from bench.spec import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "decode_roofline.py"),
                   "metrics.decode_roofline").read
