"""Device self time per iteration charged to the ``local`` layer scope
(packets moved between the Shoal kernels on one chip: the LOCAL path,
which issues no collective), mean over the cell's chips, in ms.  Ops
are charged to layers through the executed module's instruction
metadata (``layers.py``); nothing is read where the program has no
``local`` scope or the map leaves over 1% of a chip's busy time
unmapped."""

import layers


def read(run):
    if not run.trace:
        return None
    op_layers = layers.op_layers(run)
    if not op_layers or "local" not in op_layers.values():
        return None
    return layers.read(run, "local")
