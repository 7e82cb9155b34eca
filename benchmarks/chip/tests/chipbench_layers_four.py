"""Jacobi's four-kernel cell at a small size on 4 virtual CPU devices
(run by test_chipbench_layers.py in a child process): the layer map that
``layers.py`` rebuilds from the cell's configuration is the map of the
module the app's session runs."""

import random
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import chipbench_helpers as h  # noqa: E402
import layers  # noqa: E402


def main():
    import jax

    from repro.launch.hlo_analysis import op_layers

    cell = h.small_cell("jacobi-4096.4chip")
    s = cell.app.setup(cell.config, cell.traffic, h.SEED, jax.devices()[:4],
                       sample=2, rng=random.Random(h.SEED))
    s.call()
    ran = op_layers(s.fn.lower(s.st, s.blocks[1]).compile().as_text())
    rebuilt = op_layers(layers.module_text(cell.config, cell.traffic))
    assert rebuilt == ran, set(rebuilt) ^ set(ran)
    assert {"compute", "egress", "wire", "ingress", "sync"} <= set(
        rebuilt.values())
    print("four-kernel layer map matches")


if __name__ == "__main__":
    main()
