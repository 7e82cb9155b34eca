"""Compile the main path's programs for a described TPU v5e (no chip).

The TPU compiler refuses what interpret mode accepts: unaligned blocks,
kernels that overflow VMEM, programs that do not partition.  These
compiles guard the paths ``chip_smoke.py`` runs on the chip.  The
topology is described inside a fixture: only one process at a time may
load the TPU library, so nothing here touches it at import.
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.state import PgasState


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def kernel_mesh(topo):
    return lambda n, names=("kernel",): Mesh(np.array(topo.devices[:n]),
                                             names)


def _state_shapes(ctx):
    """Global PgasState shapes (leading kernel dim) sharded over ctx.mesh."""
    shd = NamedSharding(ctx.mesh, P(ctx.axes))
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((ctx.num_kernels,) + x.shape, x.dtype,
                                       sharding=shd),
        jax.eval_shape(lambda: PgasState.make(ctx.segment_words)))


def test_jacobi_stencil_4096_compiles(topo):
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.jacobi import jacobi_step
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.float32,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    hlo = jax.jit(jacobi_step).lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_jacobi_stencil_4096_bf16_compiles(topo):
    """Mosaic rotates 32-bit data only: the kernel's row shifts of a
    bfloat16 band must go through f32."""
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.jacobi import jacobi_step
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    hlo = jax.jit(jacobi_step).lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\s([a-z][\w\-]*)\((.*)")


def _instructions(hlo: str) -> dict:
    """``{name: (opcode, rest of the line)}`` of a compiled module."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = (m.group(2), m.group(3))
    return out


_SHAPE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = [a-z]\w*\[([\d,]*)\]",
                    re.M)


def _jacobi_app_hlo(monkeypatch, kernel_mesh, use_pallas, kernels=4,
                    chips=None, iters=4):
    """The compiled Jacobi app at grid 4096; ``iters=4`` keeps a solve
    loop of two trips (a single trip of two iterations is unrolled)."""
    import repro.apps.jacobi as jacobi_app
    monkeypatch.setattr(jacobi_app, "make_cpu_mesh", kernel_mesh)
    app = jacobi_app.JacobiApp(n=4096, kernels=kernels, iters=iters,
                               use_pallas=use_pallas, chips=chips)
    blocks = jax.ShapeDtypeStruct(
        (kernels, app.rows, app.n), jnp.float32,
        sharding=NamedSharding(app.mesh, P(("kernel",))))
    return app.build().lower(_state_shapes(app.ctx),
                             blocks).compile().as_text()


def _assert_stencil_reads_band_once(hlo, rows, n):
    """Each stencil call has one band-sized operand (its bitcasts are the
    same buffer): the band the solve loop carries, or the other call's
    result in a trip of two iterations, reached through copies alone;
    and no band-sized pad or fusion of layer ``compute`` is built beside
    it."""
    from repro.launch.hlo_analysis import op_layers

    layers, instrs = op_layers(hlo), _instructions(hlo)
    dims = {k: [int(d) for d in s.split(",") if d]
            for k, s in _SHAPE.findall(hlo)}
    band = lambda k: (len(dims.get(k, ())) == 2  # noqa: E731
                      and dims[k][0] >= rows - 1 and dims[k][1] == n)

    def source(a, through):
        while instrs[a][0] in through:
            a = re.findall(r"%([\w.\-]+)", instrs[a][1])[0]
        return a

    calls = {k: rest for k, (op, rest) in instrs.items()
             if op == "custom-call" and "tpu_custom_call" in rest}
    assert calls
    for rest in calls.values():
        bands = {source(a, ("bitcast",))
                 for a in re.findall(r"%([\w.\-]+)", rest.split(")")[0])
                 if band(a)}
        assert len(bands) == 1, bands
        a = source(bands.pop(), ("copy", "copy-start", "copy-done",
                                 "bitcast"))
        assert instrs[a][0] == "get-tuple-element" or a in calls, \
            (a, instrs[a])
    views = [k for k, (op, _) in instrs.items()
             if op in ("pad", "fusion") and layers[k] == "compute" and band(k)]
    assert not views, views


def _assert_ingress_has_no_loop(layers, instrs):
    """The halo stacks land in one pass: the ``ingress`` layer holds no
    ``while`` (a scan over packet rows) and no ``conditional`` (a
    handler switch), though it holds the halo writes."""
    ingress = [op for k, (op, _) in instrs.items() if layers[k] == "ingress"]
    assert "dynamic-update-slice" in ingress
    assert "while" not in ingress and "conditional" not in ingress


@pytest.mark.parametrize("use_pallas", [False, True])
def test_jacobi_app_4_kernels_compiles(kernel_mesh, monkeypatch, use_pallas):
    from repro.launch.hlo_analysis import op_layers

    hlo = _jacobi_app_hlo(monkeypatch, kernel_mesh, use_pallas)
    assert "collective-permute" in hlo
    assert ("tpu_custom_call" in hlo) == use_pallas
    # the layer scopes reach the executed module's instructions
    layers, instrs = op_layers(hlo), _instructions(hlo)
    permutes = [k for k, (op, _) in instrs.items()
                if op.startswith("collective-permute")]
    assert permutes and all(layers[k] == "wire" for k in permutes)
    # one kernel per device: no op builds an in-device move
    assert "local" not in set(layers.values())
    _assert_ingress_has_no_loop(layers, instrs)
    if use_pallas:
        calls = [(k, rest) for k, (op, rest) in instrs.items()
                 if op == "custom-call" and "tpu_custom_call" in rest]
        assert calls and all(layers[k] == "compute" for k, _ in calls)
        _assert_stencil_reads_band_once(hlo, 4096 // 4, 4096)


def test_jacobi_app_1_kernel_stencil_reads_band_once(kernel_mesh,
                                                    monkeypatch):
    """On one kernel the whole 64-MiB grid is the band: the kernel still
    reads it as its only band-sized operand, with no shifted copies."""
    hlo = _jacobi_app_hlo(monkeypatch, kernel_mesh, True, kernels=1)
    _assert_stencil_reads_band_once(hlo, 4096, 4096)


def test_jacobi_app_8_kernels_on_1_chip_compiles(kernel_mesh, monkeypatch):
    """The paper's 8 kernels on one node, on one chip: no collective is
    left (the halos take the LOCAL path, the barrier's psum over one
    device goes), the in-chip moves carry the ``local`` layer, and the
    stencil is one ``jacobi_step_pallas`` call an iteration, each over
    the 8 stacked bands."""
    from repro.launch.hlo_analysis import op_layers, parse_collectives

    hlo = _jacobi_app_hlo(monkeypatch, kernel_mesh, True, kernels=8,
                          chips=1)
    assert parse_collectives(hlo).ops == {}
    layers, instrs = op_layers(hlo), _instructions(hlo)
    assert "local" in set(layers.values())
    calls = [k for k, (op, rest) in instrs.items()
             if op == "custom-call" and "tpu_custom_call" in rest]
    # the solve loop runs two iterations a trip
    assert len(calls) == 2
    for call in calls:
        assert call.startswith("jacobi_step_pallas")
        assert layers[call] == "compute"
        assert re.search(rf"%{re.escape(call)} = f32\[8,512,4096\]", hlo)
    _assert_ingress_has_no_loop(layers, instrs)


def _computations(hlo: str) -> tuple[str, dict]:
    """The entry computation's name and ``{computation: {instruction:
    (opcode, rest of the line)}}`` of a compiled module."""
    entry, comps, current = None, {}, None
    for line in hlo.splitlines():
        if m := re.match(r"^(ENTRY )?%([\w.\-]+) ", line):
            current = m.group(2)
            comps[current] = {}
            entry = current if m.group(1) else entry
        elif (m := _INSTR.match(line)) and current is not None:
            comps[current][m.group(1)] = (m.group(2), m.group(3))
    return entry, comps


@pytest.fixture(scope="module")
def band_copies():
    """``carry_copy_ms``'s rule: the copies of the solve loop's band in
    the loop's body, as the benchmark reads them off the chip's trace."""
    import importlib.util
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "carry_copy_ms", os.path.join(bench, "metrics", "carry_copy_ms.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader.band_copies


@pytest.mark.parametrize("kernels,chips", [(1, None), (4, None), (8, 1)])
def test_jacobi_solve_loop_copies_no_band(kernel_mesh, monkeypatch,
                                          band_copies, kernels, chips):
    """The solve loop runs two iterations a trip, so the two stencil
    calls alternate the band between HBM and on-chip memory: its body
    holds both calls and no copy of the band, on one kernel, on four,
    and on eight kernels of one chip."""
    hlo = _jacobi_app_hlo(monkeypatch, kernel_mesh, True, kernels=kernels,
                          chips=chips, iters=1024)
    assert band_copies(hlo) == set()
    entry, comps = _computations(hlo)
    bodies = {m.group(1) for op, rest in comps[entry].values()
              if op == "while" and (m := re.search(r"body=%([\w.\-]+)",
                                                    rest))}
    calls = {c: [k for k, (op, rest) in instrs.items()
                 if op == "custom-call" and "tpu_custom_call" in rest]
             for c, instrs in comps.items()}
    (body, names), = ((c, ks) for c, ks in calls.items() if ks)
    assert body in bodies and len(names) == 2, (body, names)
    assert all(k.startswith("jacobi_step_pallas") for k in names)


def test_jacobi_app_1_kernel_module_unchanged(kernel_mesh, monkeypatch):
    """One kernel sends no halo, so no AM path reaches its module: it is
    instruction for instruction the recorded one (metadata aside, and
    the Pallas kernel's serialized body, which carries source paths)."""
    def stripped(hlo):
        return [re.sub(r'"body":"[^"]*"', '"body":""',
                       re.sub(r",? metadata=\{[^}]*\}", "", line)).strip()
                for line in hlo.splitlines() if _INSTR.match(line)]

    hlo = _jacobi_app_hlo(monkeypatch, kernel_mesh, True, kernels=1)
    want = os.path.join(os.path.dirname(__file__), "testdata",
                        "jacobi4096x1.v5e.instructions.txt")
    with open(want) as f:
        assert stripped(hlo) == f.read().splitlines()


def test_layer_scopes_change_no_instruction(kernel_mesh, monkeypatch):
    """Scopes are metadata: with them off, the compiled module is the
    same instruction for instruction (the Pallas kernel's body too)."""
    import repro.apps.jacobi as jacobi_app
    from repro.analysis import trace

    def strip(hlo):
        return [re.sub(r"(?<![\w])metadata=\{[^}]*\}", "", rest)
                for _, rest in _instructions(hlo).values()]

    scoped = _jacobi_app_hlo(monkeypatch, kernel_mesh, True)
    off = lambda name: contextlib.nullcontext()  # noqa: E731
    monkeypatch.setattr(trace, "layer", off)
    monkeypatch.setattr(jacobi_app, "layer", off)
    plain = _jacobi_app_hlo(monkeypatch, kernel_mesh, True)
    assert "layer." in scoped and "layer." not in plain
    assert list(_instructions(scoped)) == list(_instructions(plain))
    assert strip(scoped) == strip(plain)


def test_local_put_long_and_mixed_mailbox_compile(kernel_mesh):
    from repro.actors import Mailbox
    from repro.core import handlers as hd
    from repro.core import ops
    from repro.core.state import ShoalContext
    from repro.runtime import TCP

    local = [(0, 0)]
    ctx = ShoalContext(mesh=kernel_mesh(1), axes=("kernel",), transport=TCP,
                       segment_words=1 << 15)
    n_long = 8 * TCP.max_packet_words + 100
    rng = np.random.default_rng(0)

    def prog(st, payload):
        st = jax.tree.map(lambda x: x[0], st)
        st = ops.put_long(ctx, st, payload, local, 0, token=1)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        mb = Mailbox(ctx, local, msg_words=4, watermark=512, token=5)
        for i in range(256):
            if i % 4 == 3:
                st = mb.send_signal(st, arg=1, token=7)
            else:
                st = mb.send(st, rng.standard_normal(1 + i % 4),
                             dst_addr=20000 + 4 * i,
                             handler=hd.H_ADD if i % 2 else hd.H_WRITE)
        st = mb.flush(st)
        return jax.tree.map(lambda x: x[None], st)

    spec = P(ctx.axes)
    fn = jax.jit(jax.shard_map(prog, mesh=ctx.mesh, in_specs=(spec, P()),
                               out_specs=spec))
    payload = jax.ShapeDtypeStruct((n_long,), jnp.float32,
                                   sharding=NamedSharding(ctx.mesh, P()))
    fn.lower(_state_shapes(ctx), payload).compile()
