"""Device time that no region of the program names: operations without a
scope path (``no_op_name``: copies and loops the compiler made) and with a
path that holds no ``areal.`` component (``unscoped``: a hole in the
program's regions), over device busy time in the traced slice
(``lib/region_reduce.py``; the ``device_by_region`` line names the
largest of each).  Reads ``device_unscoped_share.rollout`` and
``.train``."""

from benchmark.lib import region_reduce


def value(ctx):
    return region_reduce.share(ctx, regions=region_reduce.UNNAMED)
