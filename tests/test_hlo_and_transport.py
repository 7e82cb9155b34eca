"""HLO collective parser unit tests + the analytic transport model."""

import pytest
from hypothesis import given, settings, strategies as st

from repro.launch.hlo_analysis import parse_collectives, split_computations
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


# -- transport / router -------------------------------------------------------

def test_router_link_classes():
    spec = ClusterSpec((2, 4), ("pod", "chip"), pod_axis="pod")
    r = Router(spec)
    assert r.classify(0, 0) == LinkClass.LOCAL
    assert r.classify(0, 1) == LinkClass.ICI         # same pod
    assert r.classify(0, 4) == LinkClass.DCN         # cross pod
    assert r.classify_pattern([(0, 1), (1, 5)]) == LinkClass.DCN
    assert r.is_pure_local([(0, 0), (1, 1)])


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
