"""Device self time per iteration charged to the ``compute`` layer scope,
which in Jacobi is the stencil (the Pallas kernel's call, which builds
its up and down rows itself, and the halo-row selects), mean over the
cell's chips, in ms.  Ops are charged to layers through the executed
module's instruction metadata (``layers.py``); nothing is read where the
program has no layer scopes or the map leaves over 1% of a chip's busy
time unmapped."""

import layers


def read(run):
    return layers.read(run, "compute")
