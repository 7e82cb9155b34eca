"""HLO collective parser unit tests + the analytic transport model."""

import pytest
from hypothesis import given, settings, strategies as st

from repro.launch.hlo_analysis import (op_layers, parse_collectives,
                                      split_computations)
from repro.runtime.router import Router
from repro.runtime.topology import ClusterSpec, neighbors_ring, pairwise
from repro.runtime.transport import (TCP, UDP, LinkClass, model_latency_s,
                                     model_throughput_Bps)

MINI_HLO = """\
HloModule jit_f

%add.1 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %r = f32[] add(%x, %y)
}

%body.2 (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[8,4]{1,0} get-tuple-element(%p), index=1
  %ar = f32[8,4]{1,0} all-reduce(%g1), replica_groups={{0,1,2,3}}, to_apply=%add.1
  %d = f32[8,8]{1,0} dot(%ar, %ar), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  %dd = f32[8,4]{1,0} slice(%d), slice={[0:8], [0:4]}
  ROOT %t = (s32[], f32[8,4]) tuple(%g0, %dd)
}

%cond.3 (p: (s32[], f32[8,4])) -> pred[] {
  %p = (s32[], f32[8,4]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(10)
  ROOT %cmp = pred[] compare(%g0, %c), direction=LT
}

ENTRY %main.4 (a: f32[8,4]) -> f32[8,4] {
  %a = f32[8,4]{1,0} parameter(0)
  %cp = f32[8,4]{1,0} collective-permute(%a), source_target_pairs={{0,1},{1,2}}
  %zero = s32[] constant(0)
  %tup = (s32[], f32[8,4]) tuple(%zero, %cp)
  %w = (s32[], f32[8,4]) while(%tup), condition=%cond.3, body=%body.2
  ROOT %out = f32[8,4]{1,0} get-tuple-element(%w), index=1
}
"""


def test_parser_trip_weighting():
    stats = parse_collectives(MINI_HLO)
    # all-reduce runs 10x (while trip count), permute once
    assert stats.ops["all-reduce"] == 10.0
    assert stats.ops["collective-permute"] == 1.0
    ar_bytes = 8 * 4 * 4
    # wire: AR 2(n-1)/n with n=4 -> 1.5x, CP 1x
    expected = 10 * ar_bytes * 1.5 + ar_bytes * 1.0
    assert stats.wire_bytes == pytest.approx(expected)
    # dot: 2 * 8*8 * 4 contraction, 10 trips
    assert stats.dot_flops == pytest.approx(10 * 2 * 64 * 4)


def test_split_computations_names():
    comps = split_computations(MINI_HLO)
    assert set(comps) == {"add.1", "body.2", "cond.3", "main.4"}


LAYERED_HLO = """
ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %collective-permute-start.2 = (f32[8], f32[8]) collective-permute-start(%p), metadata={op_name="jit(f)/shard_map/shoal.drain_deferred_acks#e5/layer.sync/layer.wire/ppermute" stack_frame_id=3}
  %psum_invariant.10 = s32[] all-reduce(%c), to_apply=%add, metadata={op_name="jit(f)/while/body/shoal.barrier#e4/layer.sync/layer.wire/psum_invariant"}
  %neg.46 = s32[] negate(%c), metadata={op_name="jit(f)/while/body/shoal.wait_replies#e2/layer.sync/neg"}
  %fusion.46 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/while/body/layer.compute/jit(jacobi_step_pallas)/slice"}
  %while.203 = (s32[], f32[8]) while(%t), condition=%c.1, body=%b.1, metadata={op_name="jit(f)/shoal.put_long_multi#e0/layer.ingress/while"}
  %add.9 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/layer.compute/transpose(jvp(layer.egress))/add"}
  %copy.194 = f32[8]{0} copy(%p)
  %mul.7 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/shoal.put_long#e1/mul"}
  %user.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/mylayer.wire/layer.computes/add"}
  ROOT %out = f32[8]{0} copy(%p), metadata={op_name="jit(f)/layer.egress/copy"}
}
"""


def test_op_layers_innermost_scope_wins():
    layers = op_layers(LAYERED_HLO)
    assert layers["collective-permute-start.2"] == "wire"
    assert layers["psum_invariant.10"] == "wire"
    assert layers["neg.46"] == "sync"
    assert layers["fusion.46"] == "compute"
    assert layers["while.203"] == "ingress"     # #e<seq> tags ignored
    assert layers["add.9"] == "egress"          # inside a transform's name
    assert layers["out"] == "egress"            # ROOT instructions too


def test_op_layers_no_metadata_or_no_layer_is_none():
    layers = op_layers(LAYERED_HLO)
    assert layers["copy.194"] is None            # compiler-made: no metadata
    assert layers["p"] is None
    assert layers["mul.7"] is None               # a shoal tag, no layer
    assert layers["user.1"] is None              # not a layer scope's name
    assert "main.9" not in layers                # computations are not ops


def test_layer_scopes_reach_metadata_but_not_lint_tags():
    import jax
    import jax.numpy as jnp

    from repro.analysis import trace

    def f(x):
        with trace.scope("shoal.put_long#e0"), trace.layer("egress"):
            y = x * 2
        with trace.layer("sync"), trace.layer("wire"):
            return y + 1

    x = jnp.ones(4)
    assert trace.recover_tags(jax.make_jaxpr(f)(x)) == {"shoal.put_long#e0": 1}
    layers = op_layers(jax.jit(f).lower(x).compile().as_text())
    assert {v for v in layers.values() if v} == {"egress", "wire"}
    # a fusion carries its root's metadata: the multiply fused into the
    # add is charged to the add's layer
    fusions = [k for k in layers if "fusion" in k]
    assert fusions and all(layers[k] == "wire" for k in fusions)
    with pytest.raises(ValueError, match="unknown layer"):
        trace.layer("kernels")


# -- transport / router -------------------------------------------------------

def test_router_link_classes():
    spec = ClusterSpec((2, 4), ("pod", "chip"), pod_axis="pod")
    r = Router(spec)
    assert r.classify(0, 0) == LinkClass.LOCAL
    assert r.classify(0, 1) == LinkClass.ICI         # same pod
    assert r.classify(0, 4) == LinkClass.DCN         # cross pod
    assert r.classify_pattern([(0, 1), (1, 5)]) == LinkClass.DCN
    assert r.is_pure_local([(0, 0), (1, 1)])


def test_colocated_placement():
    """Kernels outnumber devices: kernel k lives in slot k % 4 of device
    k // 4; pairs on one device are LOCAL, the others ICI or DCN."""
    from repro.core.address_space import GlobalAddressSpace
    from repro.core.state import ShoalContext
    from repro.runtime.topology import kernel_coords, make_cpu_mesh, split_local

    spec = ClusterSpec((2, 2), ("pod", "chip"), pod_axis="pod",
                       kernels_per_device=4)
    assert spec.num_devices == 4 and spec.num_kernels == 16
    assert kernel_coords(spec, 13) == {"pod": 1, "chip": 1}
    r = Router(spec)
    assert r.classify(4, 7) == LinkClass.LOCAL       # both on device 1
    assert r.classify(3, 4) == LinkClass.ICI         # devices 0 and 1
    assert r.classify(7, 8) == LinkClass.DCN         # pods 0 and 1
    assert r.is_pure_local([(0, 3), (5, 4)])
    assert split_local([(0, 3), (3, 4), (9, 9)], 4) == ([(0, 3), (9, 9)],
                                                       [(3, 4)])
    ctx = ShoalContext(mesh=make_cpu_mesh(1, ("kernel",)), axes=("kernel",),
                       segment_words=128, kernels_per_device=8)
    gas = GlobalAddressSpace(ctx)
    assert ctx.num_kernels == 8
    assert gas.placement(gas.global_addr(5, 7)) == (0, 5)


def test_latency_model_ordering():
    """The paper's qualitative results: async (UDP) < acked (TCP), and
    LOCAL < ICI < DCN, and latency grows with payload."""
    for link in LinkClass:
        assert (model_latency_s(UDP, link, 1024)
                < model_latency_s(TCP, link, 1024))
    for t in (TCP, UDP):
        assert (model_latency_s(t, LinkClass.LOCAL, 256)
                < model_latency_s(t, LinkClass.ICI, 256)
                < model_latency_s(t, LinkClass.DCN, 256))
        assert (model_latency_s(t, LinkClass.ICI, 8)
                < model_latency_s(t, LinkClass.ICI, 4096))


def test_throughput_model_grows_with_payload():
    small = model_throughput_Bps(TCP, LinkClass.ICI, 8)
    large = model_throughput_Bps(TCP, LinkClass.ICI, 4096)
    assert large > small
    assert large < TCP.bw_Bps[LinkClass.ICI.value]


def test_mtu_words():
    assert TCP.max_packet_words == 2250     # 9000-byte jumbo frame / 4


@settings(max_examples=25, deadline=None)
@given(n=st.integers(2, 64), shift=st.integers(1, 8))
def test_ring_pattern_is_permutation(n, shift):
    ring = neighbors_ring(n, shift)
    pairwise(ring)   # no duplicate src/dst
    assert sorted(s for s, _ in ring) == list(range(n))
    assert sorted(d for _, d in ring) == list(range(n))


def test_pairwise_rejects_duplicates():
    with pytest.raises(ValueError):
        pairwise([(0, 1), (0, 2)])


def test_segments_plan():
    from repro.core.ops import _segments
    plan = _segments(50, 16)
    assert plan == [(0, 16), (16, 16), (32, 16), (48, 2)]
    assert _segments(16, 16) == [(0, 16)]


def test_address_space_math():
    from repro.core.address_space import GlobalAddressSpace
    from repro.core.state import ShoalContext
    from repro.runtime.topology import make_cpu_mesh
    ctx = ShoalContext(mesh=make_cpu_mesh(1, ("kernel",)), axes=("kernel",),
                       segment_words=128)
    gas = GlobalAddressSpace(ctx)
    g = gas.global_addr(0, 37)
    assert gas.owner_of(g) == 0 and gas.local_offset(g) == 37
    with pytest.raises(ValueError):
        gas.global_addr(0, 128)
