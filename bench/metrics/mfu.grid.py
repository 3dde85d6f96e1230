"""The sweep grid's share of the chip's bf16 peak (``bench/readers.py``).
The loop's ``unit_flops`` is what one grid point's round requires: the
devices' gradients, the dense projection and AMP decode of the analog
scheme, the test evaluation (``bench/counts.py``)."""
from bench.readers import mfu as read  # noqa: F401
