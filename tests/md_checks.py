"""Multi-device semantic checks for the Shoal library, the trainer
backends, and elastic restart.  Run by tests/test_multidevice.py in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.core import collectives as coll
from repro.core import handlers as hd
from repro.core import humboldt, ops
from repro.core.address_space import GlobalAddressSpace
from repro.core.state import ShoalContext
from repro.runtime import TCP, UDP, make_cpu_mesh
from repro.runtime.topology import make_mesh

N = 8
RING = [(i, (i + 1) % N) for i in range(N)]


def check(name):
    print(f"[md] {name}", flush=True)


def test_put_long_ring():
    check("put_long ring + wait_replies + barrier")
    mesh = make_cpu_mesh(N, ("kernel",))
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=TCP,
                       segment_words=64)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        me = ctx.my_id()
        pay = (jnp.arange(4, dtype=jnp.float32) + 1) * (me + 1).astype(jnp.float32)
        st = ops.put_long(ctx, st, pay, RING, dst_addr=10, token=1)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        st = ops.barrier(ctx, st)
        return st

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    seg = np.asarray(st.segment)
    for k in range(N):
        src = (k - 1) % N
        np.testing.assert_allclose(seg[k, 10:14], (np.arange(4) + 1) * (src + 1))
    assert (np.asarray(st.error) == 0).all()
    assert (np.asarray(st.barrier_epoch) == 1).all()
    assert (np.asarray(st.credits) == 0).all()     # drained


def test_accumulate_and_get():
    check("put_long H_ADD + get_medium + get_long")
    mesh = make_cpu_mesh(N, ("kernel",))
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=TCP,
                       segment_words=64)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        me = ctx.my_id()
        st = ops.put_long(ctx, st, jnp.ones(2, jnp.float32) * (me + 1).astype(jnp.float32),
                          RING, dst_addr=0, handler=hd.H_ADD, token=1)
        st = ops.put_long(ctx, st, jnp.ones(2, jnp.float32), RING, dst_addr=0,
                          handler=hd.H_ADD, token=1)
        st = ops.wait_replies(ctx, st, token=1, n=2)
        # fetch my successor's segment[0:2]
        st, data = ops.get_medium(ctx, st, RING, src_addr=0, nwords=2, token=2)
        st = ops.wait_replies(ctx, st, token=2, n=1)
        seg = jax.lax.dynamic_update_slice(st.segment, data, (30,))
        from repro.core.gascore import dataclasses_replace
        st = dataclasses_replace(st, segment=seg)
        # one-sided read into local segment at 40
        st = ops.get_long(ctx, st, RING, src_addr=0, nwords=2, dst_addr=40,
                          token=3)
        st = ops.wait_replies(ctx, st, token=3, n=1)
        return st

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    seg = np.asarray(st.segment)
    for k in range(N):
        src = (k - 1) % N
        expect = src + 2.0
        np.testing.assert_allclose(seg[k, 0:2], expect)      # accumulated
        succ = (k + 1) % N
        np.testing.assert_allclose(seg[k, 30:32], k + 2.0)   # what succ holds
        np.testing.assert_allclose(seg[k, 40:42], k + 2.0)
    assert (np.asarray(st.error) == 0).all()


def test_strided_vectored():
    check("put_long_strided + put_long_vectored")
    mesh = make_cpu_mesh(N, ("kernel",))
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=TCP,
                       segment_words=128)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        me1 = (ctx.my_id() + 1).astype(jnp.float32)
        pay = jnp.arange(6, dtype=jnp.float32) + 10 * me1
        st = ops.put_long_strided(ctx, st, pay, RING, dst_addr=4, stride=10,
                                  blk_words=2, nblocks=3, token=1)
        st = ops.put_long_vectored(ctx, st,
                                   [jnp.full(2, me1), jnp.full(3, -me1)],
                                   RING, dst_addrs=[50, 60], token=2)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        st = ops.wait_replies(ctx, st, token=2, n=1)
        return st

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    seg = np.asarray(st.segment)
    for k in range(N):
        src1 = ((k - 1) % N) + 1
        base = np.arange(6) + 10 * src1
        np.testing.assert_allclose(seg[k, 4:6], base[0:2])
        np.testing.assert_allclose(seg[k, 14:16], base[2:4])
        np.testing.assert_allclose(seg[k, 24:26], base[4:6])
        np.testing.assert_allclose(seg[k, 50:52], src1)
        np.testing.assert_allclose(seg[k, 60:63], -src1)
    assert (np.asarray(st.error) == 0).all()


def test_mtu_segmentation():
    check(">MTU segmentation (the paper's jumbo-frame limit, implemented)")
    mesh = make_cpu_mesh(N, ("kernel",))
    import dataclasses
    tiny_tcp = dataclasses.replace(TCP, max_packet_bytes=64)   # 16 words
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=tiny_tcp,
                       segment_words=128)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        me1 = (ctx.my_id() + 1).astype(jnp.float32)
        pay = jnp.arange(50, dtype=jnp.float32) + 100 * me1
        st = ops.put_long(ctx, st, pay, RING, dst_addr=8, token=1)
        # 50 words / 16-word packets -> 4 packets, ONE coalesced reply:
        # only the final segment of a message is acked
        st = ops.wait_replies(ctx, st, token=1, n=1)
        return st

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    seg = np.asarray(st.segment)
    for k in range(N):
        src1 = ((k - 1) % N) + 1
        np.testing.assert_allclose(seg[k, 8:58], np.arange(50) + 100 * src1)
    assert (np.asarray(st.error) == 0).all(), \
        "expected one coalesced reply per message"


def test_mtu_segmentation_edge():
    check(">MTU put flush against the segment end (partial final packet)")
    mesh = make_cpu_mesh(N, ("kernel",))
    import dataclasses
    tiny_tcp = dataclasses.replace(TCP, max_packet_bytes=64)   # 16 words
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=tiny_tcp,
                       segment_words=128)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        me1 = (ctx.my_id() + 1).astype(jnp.float32)
        pay = jnp.arange(50, dtype=jnp.float32) + 100 * me1
        # 78 + 50 = 128: the partial 2-word final packet lands flush
        # against the segment end
        st = ops.put_long(ctx, st, pay, RING, dst_addr=78, token=1)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        return st

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    seg = np.asarray(st.segment)
    for k in range(N):
        src1 = ((k - 1) % N) + 1
        np.testing.assert_allclose(seg[k, 78:128], np.arange(50) + 100 * src1)
    assert (np.asarray(st.error) == 0).all()


def test_mtu_gets_and_strided():
    check(">MTU get_medium / get_long / put_long_strided (batched plans)")
    mesh = make_cpu_mesh(N, ("kernel",))
    import dataclasses
    tiny_tcp = dataclasses.replace(TCP, max_packet_bytes=64)   # 16 words
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=tiny_tcp,
                       segment_words=256)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        from repro.core.gascore import dataclasses_replace
        me1 = (ctx.my_id() + 1).astype(jnp.float32)
        # seed my own segment [0, 50) with a recognizable ramp
        ramp = jnp.arange(50, dtype=jnp.float32) + 100 * me1
        st = dataclasses_replace(
            st, segment=jax.lax.dynamic_update_slice(st.segment, ramp, (0,)))
        # 50-word get_medium: 4 request packets, one batched response,
        # ONE credit for the whole message
        st, data = ops.get_medium(ctx, st, RING, src_addr=0, nwords=50,
                                  token=2)
        st = ops.wait_replies(ctx, st, token=2, n=1)
        st = dataclasses_replace(
            st, segment=jax.lax.dynamic_update_slice(st.segment, data, (60,)))
        # 50-word get_long into my segment at 120
        st = ops.get_long(ctx, st, RING, src_addr=0, nwords=50, dst_addr=120,
                          token=3)
        st = ops.wait_replies(ctx, st, token=3, n=1)
        # strided put: 10 blocks of 3 words, stride 5 -> lands at
        # 180 + i*5; 30 words > 16-word MTU so it segments at block
        # granularity (5 blocks per packet, 2 packets, one reply)
        pay = jnp.arange(30, dtype=jnp.float32) + 1000 * me1
        st = ops.put_long_strided(ctx, st, pay, RING, dst_addr=180, stride=5,
                                  blk_words=3, nblocks=10, token=4)
        st = ops.wait_replies(ctx, st, token=4, n=1)
        return st

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    seg = np.asarray(st.segment)
    for k in range(N):
        succ1 = ((k + 1) % N) + 1      # gets fetch from my successor
        pred1 = ((k - 1) % N) + 1      # strided put arrives from predecessor
        np.testing.assert_allclose(seg[k, 60:110],
                                   np.arange(50) + 100 * succ1)
        np.testing.assert_allclose(seg[k, 120:170],
                                   np.arange(50) + 100 * succ1)
        for i in range(10):
            np.testing.assert_allclose(
                seg[k, 180 + 5 * i:183 + 5 * i],
                np.arange(3) + 3 * i + 1000 * pred1)
    assert (np.asarray(st.error) == 0).all()


def test_async_udp_semantics():
    check("async (UDP) suppresses replies; wait flags underflow")
    mesh = make_cpu_mesh(N, ("kernel",))
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=UDP,
                       segment_words=32)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        st = ops.put_long(ctx, st, jnp.ones(2, jnp.float32), RING,
                          dst_addr=0, token=1)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        return st

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    assert (np.asarray(st.error) == 1).all()
    np.testing.assert_allclose(np.asarray(st.segment)[:, 0:2], 1.0)


def test_put_long_multi_semantics():
    check("put_long_multi: disjoint rings merge, interleaved stacks land")
    import dataclasses
    mesh = make_cpu_mesh(N, ("kernel",))
    tiny = dataclasses.replace(TCP, max_packet_bytes=64)   # 16-word MTU
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=tiny,
                       segment_words=128)
    gas = GlobalAddressSpace(ctx)
    even = [(i, i + 1) for i in range(0, N, 2)]        # srcs/dsts disjoint
    odd = [(i, (i + 1) % N) for i in range(1, N, 2)]   # from even's: merge

    def prog(st):
        me = ctx.my_id().astype(jnp.float32)
        # 40 words = 3 rows at the 16-word MTU, 10 words = 1 row; the
        # two stacks interleave in one union-permutation collective
        items = [(jnp.arange(40, dtype=jnp.float32) + 1000.0 * me, even, 8),
                 (jnp.arange(10, dtype=jnp.float32) - 1000.0 * me, odd, 64)]
        st = ops.put_long_multi(ctx, st, items, token=4)
        return ops.wait_replies(ctx, st, token=4, n=1)

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    seg = np.asarray(st.segment)
    for k in range(N):
        src = (k - 1) % N
        if k % 2 == 1:     # receives the even-ring item
            np.testing.assert_allclose(seg[k, 8:48],
                                       np.arange(40.0) + 1000.0 * src)
        else:              # receives the odd-ring item
            np.testing.assert_allclose(seg[k, 64:74],
                                       np.arange(10.0) - 1000.0 * src)
    # every kernel sent exactly one item and the ONE counted group reply
    # returned exactly one credit for it, drained by the wait
    assert (np.asarray(st.credits) == 0).all()
    assert (np.asarray(st.error) == 0).all()


def test_put_long_multi_alias_guard():
    check("put_long_multi: cross-item overlap raises VectoredAliasError")
    mesh = make_cpu_mesh(N, ("kernel",))
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=TCP,
                       segment_words=64)
    gas = GlobalAddressSpace(ctx)
    # both items land on kernel 1; [8, 12) and [10, 14) overlap, so the
    # value at [10, 12) depends on stack order
    items_of = lambda: [(jnp.ones(4, jnp.float32), [(0, 1)], 8),
                        (jnp.full((4,), 2.0), [(2, 1)], 10)]

    def prog(st):
        return ops.put_long_multi(ctx, st, items_of(), token=1,
                                  asynchronous=True)

    try:
        jax.jit(gas.spmd(prog)).lower(gas.make_global_state())
        raised = False
    except ops.VectoredAliasError:
        raised = True
    assert raised, "overlapping put_long_multi items must raise"

    from repro.analysis import waiver

    def prog_waived(st):
        with waiver("alias test: last-writer-wins is intended"):
            return ops.put_long_multi(ctx, st, items_of(), token=1,
                                      asynchronous=True)

    jax.jit(gas.spmd(prog_waived)).lower(gas.make_global_state())


def test_piggyback_steady_loop():
    check("reply piggybacking: 2 CPs/iteration steady state, clean drain")
    from repro.analysis import hlo_budget
    mesh = make_cpu_mesh(N, ("kernel",))
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=TCP,
                       segment_words=64)
    gas = GlobalAddressSpace(ctx)
    rring = [((i + 1) % N, i) for i in range(N)]
    iters = 5

    def prog(st):
        def body(st, it):
            # forward puts defer acks (token 1); the reverse packet
            # piggybacks them home, and vice versa (token 2) — zero ack
            # collectives inside the loop
            items = [(jnp.full((4,), 1.0 + it), RING, 8),
                     (jnp.full((4,), 101.0 + it), rring, 16)]
            st = ops.put_long_multi(ctx, st, items, tokens=[1, 2],
                                    defer_ack=True, piggyback_tokens=[2, 1])
            # iteration k's acks ride iteration k+1's packets
            ready = (it > 0).astype(jnp.int32)
            st = ops.wait_replies(ctx, st, token=1, n=ready)
            st = ops.wait_replies(ctx, st, token=2, n=ready)
            return st, ()

        st, _ = jax.lax.scan(body, st, jnp.arange(iters))
        # loop exit: the final iteration's acks are still ledgered at
        # the receivers; one drain per link ships them home
        st = ops.drain_deferred_acks(ctx, st, rring, token=1)
        st = ops.drain_deferred_acks(ctx, st, RING, token=2)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        st = ops.wait_replies(ctx, st, token=2, n=1)
        return st

    jitted = jax.jit(gas.spmd(prog))
    st0 = gas.make_global_state()
    st = jitted(st0)
    seg = np.asarray(st.segment)
    for k in range(N):
        np.testing.assert_allclose(seg[k, 8:12], float(iters))       # 1+it
        np.testing.assert_allclose(seg[k, 16:20], 100.0 + iters)
    # no ack stranded: every deferred ack was piggybacked or drained,
    # every credit consumed, no underflow tripped
    assert (np.asarray(st.deferred_acks) == 0).all()
    assert (np.asarray(st.credits) == 0).all()
    assert (np.asarray(st.error) == 0).all()
    # the whole program is 2 CPs per iteration (trip-weighted) + the 2
    # one-off drains — the per-iteration ack collectives are GONE
    stats = hlo_budget.measure(jitted.lower(st0).compile().as_text())
    cps = stats.ops.get("collective-permute", 0.0)
    assert cps == 2 * iters + 2, f"steady state regressed: {cps} CPs"


def test_bf16_wire_accounting():
    check("sub-32-bit (bf16) split fallback: bytes-on-wire tx accounting")
    mesh = make_cpu_mesh(N, ("kernel",))
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=TCP,
                       segment_words=64)
    gas = GlobalAddressSpace(ctx, dtype=jnp.bfloat16)

    def prog(st):
        me1 = (ctx.my_id() + 1).astype(jnp.bfloat16)
        pay = jnp.full((10,), 1.0, jnp.bfloat16) * me1
        st = ops.put_long(ctx, st, pay, RING, dst_addr=4, token=1)
        return ops.wait_replies(ctx, st, token=1, n=1)

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    seg = np.asarray(st.segment.astype(jnp.float32))
    for k in range(N):
        np.testing.assert_allclose(seg[k, 4:14], ((k - 1) % N) + 1.0)
    # 10 bf16 words are 20 bytes = 5 int32 wire words, not 10: the old
    # element-count accounting overstated sub-32-bit wire volume 2x
    assert (np.asarray(st.tx_words) == 5).all(), np.asarray(st.tx_words)
    assert (np.asarray(st.error) == 0).all()


def test_humboldt_two_sided():
    check("HUMboldt 4-phase send/recv")
    mesh = make_cpu_mesh(N, ("kernel",))
    ctx = ShoalContext(mesh=mesh, axes=("kernel",), transport=TCP,
                       segment_words=32)
    gas = GlobalAddressSpace(ctx)

    def prog(st):
        me1 = (ctx.my_id() + 1).astype(jnp.float32)
        st, recv = humboldt.sendrecv(ctx, st, me1 * jnp.ones(3), RING, token=4)
        from repro.core.gascore import dataclasses_replace
        st = dataclasses_replace(
            st, segment=jax.lax.dynamic_update_slice(st.segment, recv, (4,)))
        st = ops.wait_replies(ctx, st, token=4, n=1)
        return st

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    seg = np.asarray(st.segment)
    for k in range(N):
        np.testing.assert_allclose(seg[k, 4:7], ((k - 1) % N) + 1)
    assert (np.asarray(st.error) == 0).all()


def test_ring_collectives():
    check("ring collectives vs lax references")
    mesh = make_cpu_mesh(N, ("kernel",))
    xs = jnp.asarray(np.random.default_rng(0).standard_normal((N, 37)),
                     jnp.float32)

    def ar(x):
        return coll.ring_all_reduce(x, ("kernel",), N)

    out = jax.jit(shard_map(ar, mesh=mesh, in_specs=P("kernel"),
                                out_specs=P("kernel")))(xs)
    np.testing.assert_allclose(np.asarray(out),
                               np.tile(np.asarray(xs).sum(0), (N, 1)),
                               rtol=1e-5)

    def rs(x):
        return coll.ring_reduce_scatter(x, ("kernel",), N)

    xs2 = jnp.asarray(np.random.default_rng(1).standard_normal((N, 40)),
                      jnp.float32)
    out = jax.jit(shard_map(rs, mesh=mesh, in_specs=P("kernel"),
                                out_specs=P("kernel")))(xs2)
    np.testing.assert_allclose(np.asarray(out).reshape(N, 5),
                               np.asarray(xs2).sum(0).reshape(N, 5), rtol=1e-5)

    def bc(x):
        return coll.broadcast_from(x, ("kernel",), N, root=5)

    out = jax.jit(shard_map(bc, mesh=mesh, in_specs=P("kernel"),
                                out_specs=P("kernel")))(xs)
    np.testing.assert_allclose(np.asarray(out),
                               np.tile(np.asarray(xs)[5], (N, 1)))


def test_trainer_backends_agree():
    check("xla vs shoal trainer backends + int8 EF + quorum")
    from repro.models.model import ModelConfig, build_model
    from repro.optim.adamw import AdamWConfig
    from repro.training.train import Trainer, TrainerConfig
    from repro.data.pipeline import DataConfig, TokenPipeline

    mesh = make_cpu_mesh(N, ("kernel",))
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                      dtype=jnp.float32)
    batch, _ = TokenPipeline(DataConfig(vocab=256, batch=8, seq=32,
                                        seed=1)).next_batch(0)
    b = {k: jax.device_put(v, NamedSharding(mesh, P(("data",))))
         for k, v in batch.items()}

    m1 = build_model(cfg, mesh=mesh, dp_axes=("data",))
    tr1 = Trainer(m1, AdamWConfig(lr=1e-3), TrainerConfig(donate=False))
    st1 = tr1.init_state(jax.random.PRNGKey(0))
    s1, met1 = tr1.make_train_step()(st1, b)

    m2 = build_model(cfg, mesh=mesh, dp_axes=())
    tr2 = Trainer(m2, AdamWConfig(lr=1e-3),
                  TrainerConfig(comm_backend="shoal", donate=False),
                  dp_axes=("data",))
    st2 = tr2.init_state(jax.random.PRNGKey(0))
    s2, met2 = tr2.make_train_step()(st2, b)
    assert abs(float(met1["loss"]) - float(met2["loss"])) < 1e-4
    deltas = jax.tree.map(lambda a, c: float(jnp.max(jnp.abs(a - c))),
                          s1.params, s2.params)
    assert max(jax.tree.leaves(deltas)) < 1e-4

    tr3 = Trainer(m2, AdamWConfig(lr=1e-3),
                  TrainerConfig(comm_backend="shoal", grad_compression=True,
                                donate=False), dp_axes=("data",))
    st3 = tr3.init_state(jax.random.PRNGKey(0))
    s3, met3 = tr3.make_train_step()(st3, b)
    deltas3 = jax.tree.map(lambda a, c: float(jnp.max(jnp.abs(a - c))),
                           s1.params, s3.params)
    assert max(jax.tree.leaves(deltas3)) < 5e-2   # int8 quantization error

    # quorum DP: dropping one rank = mean over survivors
    from repro.training.elastic import quorum_mean_grads
    def qfn(g, live):
        out, n_live = quorum_mean_grads({"g": g}, live, ("data",))
        return out["g"], n_live
    g = jnp.asarray(np.arange(2 * 3, dtype=np.float32).reshape(2, 3))
    live = jnp.asarray([1.0, 0.0])
    out, n_live = jax.jit(shard_map(
        qfn, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data", None)) if False else (P("data"), P("data"))))(g, live)
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(g)[0])
    assert float(np.asarray(n_live)[0]) == 1.0


def test_elastic_reshard():
    check("checkpoint save on 8-way mesh, restore on 4-way mesh")
    from repro.checkpoint import CheckpointManager
    mesh8 = make_mesh((8,), ("data",))
    x = jnp.arange(64.0).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh8, P("data", None)))
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(7, {"w": xs}, extras={"data_step": 123})
        devs = jax.devices()[:4]
        mesh4 = jax.sharding.Mesh(np.asarray(devs).reshape(4), ("data",))
        tree, extras = mgr.restore(
            {"w": x}, shardings={"w": NamedSharding(mesh4, P("data", None))},
            verify=True)
        assert extras["data_step"] == 123
        np.testing.assert_array_equal(np.asarray(tree["w"]), np.asarray(x))
        assert len(tree["w"].sharding.device_set) == 4


def test_ring_attention_exact():
    check("ring attention (seq-parallel, one-sided-put KV rotation)")
    from repro.models.ring_attention import ring_attention
    from repro.models.attention import _attend
    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    B, S, K, G, dh = 2, 64, 2, 3, 16
    q = jnp.asarray(rng.standard_normal((B, S, K, G, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, K, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, K, dh)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    out = jax.jit(lambda *a: ring_attention(mesh, "model", ("data",), *a))(
        q, k, v, pos)
    want = _attend(q, k, v, pos, pos, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_seq_shard_model_exact():
    check("seq_shard (ring) model forward+grad vs baseline")
    import dataclasses
    from repro.models.model import ModelConfig, build_model
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                      dtype=jnp.float32, tp=False, seq_shard=True)
    m1 = build_model(cfg, mesh=mesh, dp_axes=("data",))
    m2 = build_model(dataclasses.replace(cfg, seq_shard=False))
    params = m2.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 32)),
                       jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    l1 = jax.jit(m1.loss)(params, batch)
    l2 = jax.jit(m2.loss)(params, batch)
    assert abs(float(l1) - float(l2)) < 1e-4
    g1 = jax.jit(jax.grad(m1.loss))(params, batch)
    g2 = jax.jit(jax.grad(m2.loss))(params, batch)
    d = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g1, g2)))
    assert d < 1e-4, d


def test_moe_dispatch_variants_exact():
    check("EP island dispatch variants (psum/rs/a2a) vs oracle")
    import dataclasses
    from repro.models.model import ModelConfig, build_model
    from repro.models.moe import MoEDims
    mesh = make_mesh((2, 4), ("data", "model"))
    base = MoEDims(n_experts=8, top_k=2, d_ff_expert=64, n_shared=1,
                   capacity_factor=16.0)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, (4, 32)),
                       jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    cfg0 = ModelConfig(name="tm", family="moe", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=4, d_ff=128, vocab=128,
                       fsdp=True, aux_loss_weight=0.0, moe=base,
                       dtype=jnp.float32)
    oracle = build_model(dataclasses.replace(cfg0, fsdp=False))
    params = oracle.init(jax.random.PRNGKey(1))
    l_ref = float(jax.jit(oracle.loss)(params, batch))
    for dispatch, seq in (("psum", False), ("rs", True), ("a2a", True)):
        cfg = dataclasses.replace(
            cfg0, seq_shard=seq,
            moe=dataclasses.replace(base, dispatch=dispatch))
        m = build_model(cfg, mesh=mesh, dp_axes=("data",))
        l = float(jax.jit(m.loss)(params, batch))
        assert abs(l - l_ref) < 5e-5, (dispatch, l, l_ref)


def test_gascore_rdma_ring():
    check("Pallas RDMA ring all-reduce (the literal GAScore) vs psum")
    from repro.kernels.gascore_dma import ring_allreduce_dma
    mesh = make_mesh((8,), ("x",))
    for chunk, dt, tol in [(128, jnp.float32, 1e-5), (64, jnp.bfloat16, 5e-2)]:
        x = jnp.asarray(np.random.default_rng(0).standard_normal(8 * chunk),
                        dt)
        got = np.asarray(ring_allreduce_dma(mesh, "x", x, interpret=True),
                         np.float32).reshape(8, chunk)
        want = np.asarray(x, np.float32).reshape(8, chunk).sum(0)
        for r in range(8):
            np.testing.assert_allclose(got[r], want, rtol=tol, atol=tol)


def test_pipeline_parallel():
    check("2-stage pipeline over the pod axis (Medium-AM handoffs)")
    from repro.training.pipeline import pipeline_apply, split_stages
    mesh = make_mesh((2, 4), ("pod", "chip"))
    rng = np.random.default_rng(0)
    L, d = 4, 16
    w = jnp.asarray(rng.standard_normal((L, d, d)) * 0.3, jnp.float32)

    def stage_fn(pslice, x):          # pslice: (L/2, d, d)
        def body(x, wl):
            return jnp.tanh(x @ wl), ()
        x, _ = jax.lax.scan(body, x, pslice["w"])
        return x

    M, mb = 3, 5
    xs = jnp.asarray(rng.standard_normal((M, mb, d)), jnp.float32)
    out = jax.jit(lambda p, x: pipeline_apply(
        mesh, "pod", stage_fn, p, x))(split_stages({"w": w}, 2), xs)

    # sequential reference
    ref = xs
    for l in range(L):
        ref = jnp.tanh(ref @ w[l])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def main():
    test_put_long_ring()
    test_accumulate_and_get()
    test_strided_vectored()
    test_mtu_segmentation()
    test_mtu_segmentation_edge()
    test_mtu_gets_and_strided()
    test_async_udp_semantics()
    test_put_long_multi_semantics()
    test_put_long_multi_alias_guard()
    test_piggyback_steady_loop()
    test_bf16_wire_accounting()
    test_humboldt_two_sided()
    test_ring_collectives()
    test_trainer_backends_agree()
    test_elastic_reshard()
    test_ring_attention_exact()
    test_seq_shard_model_exact()
    test_moe_dispatch_variants_exact()
    test_gascore_rdma_ring()
    test_pipeline_parallel()
    print("MD_CHECKS_ALL_PASS")


if __name__ == "__main__":
    main()
