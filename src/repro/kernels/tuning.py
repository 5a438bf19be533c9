"""The two static widths of the radix / bucketing kernel family, the same
on every backend.

* ``RADIX_BITS`` — digits per LSD counting-sort pass (``radix_sort``): a
  32-bit word takes four passes, each with a ``2**8``-wide one-hot;
* ``TILE`` — rows per Pallas grid step (``hash_partition``,
  ``fused_bucketing``, ``radix_sort``), a multiple of the 128-row
  ``tile_scan.CHUNK``.

An explicit ``radix_bits=`` / ``tile=`` argument to an op wins.
"""
RADIX_BITS = 8
TILE = 1024
