"""Share of the traced window, over whole calls of the grid's programs, in
which no operation ran on the chips (``bench/readers.py``)."""
from bench.readers import idle_frac as read  # noqa: F401
