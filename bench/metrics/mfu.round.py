"""The round program's share of the chips' bf16 peak (``bench/readers.py``).
The loop's ``unit_flops`` is what a round requires: model forward and
backward at 6 N tokens, the devices' blocked projections, the server's AMP
decode counted once (``bench/counts.py``)."""
from bench.readers import mfu as read  # noqa: F401
