"""Tools a benchmark PR runs by hand on the chip; no run uses them."""
