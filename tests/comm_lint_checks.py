"""shoal-lint behavioural checks (pass 1 + registry + host debug path).

Run by tests/test_comm_lint.py in a subprocess with 8 host devices.
Exercises every rule against small programs built from the real op
layer — including the PR 6 overlapping-strided-put race on its pre-fix
(unordered vectorized ingress) path, which the analyzer must flag — and
asserts all shipped registry entry points lint clean.
"""

import dataclasses

import jax
import jax.numpy as jnp

from repro import analysis
from repro.analysis import jaxpr_lint, registry
from repro.core import ops
from repro.core.address_space import GlobalAddressSpace
from repro.core.state import ShoalContext, WaitUnderflowError, raise_on_error
from repro.runtime import TCP, UDP
from repro.runtime.topology import make_cpu_mesh

N = 8
RING = [(i, (i + 1) % N) for i in range(N)]
TINY_TCP = dataclasses.replace(TCP, max_packet_bytes=64)


def make(transport=TCP, segment_words=128):
    ctx = ShoalContext(mesh=make_cpu_mesh(N, ("kernel",)), axes=("kernel",),
                       transport=transport, segment_words=segment_words)
    return ctx, GlobalAddressSpace(ctx)


def lint(gas, prog, name):
    return jaxpr_lint.lint(gas.spmd(prog), gas.make_global_state(),
                           name=name)


def check(name, ok, detail=""):
    assert ok, f"{name} FAILED {detail}"
    print(f"[comm-lint] {name} ok {detail}")


def rules_of(rep, severity=None):
    return [f.rule for f in rep.findings
            if not f.waived and (severity is None or f.severity == severity)]


# --------------------------------------------------------------------------
# R1: the PR 6 strided race class (regression) + unordered write pairs
# --------------------------------------------------------------------------

def test_r1_strided_prefix_race():
    """overlap=False forces the pre-fix vectorized ingress on aliasing
    blocks — the exact race PR 6 fixed.  The analyzer must flag it."""
    ctx, gas = make()
    pay = jnp.arange(16, dtype=jnp.float32)

    def racy(st):
        st = ops.put_long_strided(ctx, st, pay, RING, dst_addr=0, stride=2,
                                  blk_words=4, nblocks=4, overlap=False,
                                  token=1)
        return ops.wait_replies(ctx, st, token=1, n=1)

    rep = lint(gas, racy, "strided-prefix-race")
    check("R1 strided pre-fix race flagged", rules_of(rep) == ["R1"],
          f"(findings: {[f.render() for f in rep.findings]})")

    def fixed(st):
        st = ops.put_long_strided(ctx, st, pay, RING, dst_addr=0, stride=2,
                                  blk_words=4, nblocks=4, token=1)
        return ops.wait_replies(ctx, st, token=1, n=1)

    rep = lint(gas, fixed, "strided-ordered")
    check("R1 ordered strided ingress clean", rep.ok,
          f"(findings: {[f.render() for f in rep.findings]})")


def test_r1_unordered_write_pair():
    ctx, gas = make()
    pay = jnp.arange(8, dtype=jnp.float32)

    def racy(st):
        st = ops.put_long(ctx, st, pay, RING, dst_addr=4, token=1)
        st = ops.put_long(ctx, st, pay + 1, RING, dst_addr=8, token=2)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        return ops.wait_replies(ctx, st, token=2, n=1)

    rep = lint(gas, racy, "overlap-pair")
    check("R1 unordered overlapping puts flagged", "R1" in rules_of(rep))

    def ordered(st):
        st = ops.put_long(ctx, st, pay, RING, dst_addr=4, token=1)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        st = ops.put_long(ctx, st, pay + 1, RING, dst_addr=8, token=2)
        return ops.wait_replies(ctx, st, token=2, n=1)

    rep = lint(gas, ordered, "overlap-pair-waited")
    check("R1 wait-ordered overlapping puts clean", rep.ok,
          f"(findings: {[f.render() for f in rep.findings]})")

    def disjoint(st):
        st = ops.put_long(ctx, st, pay, RING, dst_addr=0, token=1)
        st = ops.put_long(ctx, st, pay + 1, RING, dst_addr=16, token=2)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        return ops.wait_replies(ctx, st, token=2, n=1)

    rep = lint(gas, disjoint, "disjoint-pair")
    check("R1 disjoint puts clean", rep.ok)


# --------------------------------------------------------------------------
# R2: get of a range with an in-flight put
# --------------------------------------------------------------------------

def test_r2_get_vs_inflight_put():
    ctx, gas = make()
    pay = jnp.arange(8, dtype=jnp.float32)

    def racy(st):
        st = ops.put_long(ctx, st, pay, RING, dst_addr=4, token=1)
        st, _ = ops.get_medium(ctx, st, RING, src_addr=6, nwords=4, token=2)
        st = ops.wait_replies(ctx, st, token=2, n=1)
        return ops.wait_replies(ctx, st, token=1, n=1)

    rep = lint(gas, racy, "get-inflight")
    check("R2 get with in-flight put flagged", "R2" in rules_of(rep))

    def safe(st):
        st = ops.put_long(ctx, st, pay, RING, dst_addr=4, token=1)
        st = ops.wait_replies(ctx, st, token=1, n=1)
        st, _ = ops.get_medium(ctx, st, RING, src_addr=6, nwords=4, token=2)
        return ops.wait_replies(ctx, st, token=2, n=1)

    rep = lint(gas, safe, "get-after-wait")
    check("R2 get after wait clean", rep.ok,
          f"(findings: {[f.render() for f in rep.findings]})")


# --------------------------------------------------------------------------
# R3: credit flow — underflow, leak, double-spend
# --------------------------------------------------------------------------

def test_r3_credit_flow():
    ctx, gas = make()
    pay = jnp.arange(4, dtype=jnp.float32)

    def underflow(st):
        st = ops.put_long(ctx, st, pay, RING, dst_addr=0, token=1)
        return ops.wait_replies(ctx, st, token=1, n=2)

    rep = lint(gas, underflow, "underflow")
    check("R3 wait underflow flagged",
          rules_of(rep, analysis.ERROR) == ["R3"])

    def leak(st):
        return ops.put_long(ctx, st, pay, RING, dst_addr=0, token=1)

    rep = lint(gas, leak, "leak")
    check("R3 leaked credit warned",
          rules_of(rep, analysis.WARNING) == ["R3"])

    def double_spend(st):
        a = ctx.mailbox(RING, msg_words=4, token=3)
        b = ctx.mailbox(RING, msg_words=4, token=3)
        st = a.send(st, pay, dst_addr=0)
        st = a.flush(st)
        st = b.send(st, pay, dst_addr=16)
        st = b.flush(st)
        return ops.wait_replies(ctx, st, token=3, n=2)

    rep = lint(gas, double_spend, "double-spend")
    check("R3 cross-mailbox token double-spend warned",
          "R3" in rules_of(rep, analysis.WARNING))


# --------------------------------------------------------------------------
# R4: out-of-bounds + vectored aliasing (satellite: named ValueError)
# --------------------------------------------------------------------------

def test_r4_oob_and_vectored_alias():
    ctx, gas = make()

    def oob(st):
        st = ops.put_long(ctx, st, jnp.arange(50, dtype=jnp.float32), RING,
                          dst_addr=100, token=1)
        return ops.wait_replies(ctx, st, token=1, n=1)

    rep = lint(gas, oob, "oob")
    check("R4 out-of-bounds put flagged", "R4" in rules_of(rep))

    blocks = [jnp.ones(4, jnp.float32), jnp.ones(4, jnp.float32)]

    def aliasing(st):
        return ops.put_long_vectored(ctx, st, blocks, RING,
                                     dst_addrs=[8, 10], token=1,
                                     asynchronous=True)

    try:
        lint(gas, aliasing, "vectored-alias")
        raise AssertionError("overlapping dst_addrs did not raise")
    except ops.VectoredAliasError as e:
        check("R4 VectoredAliasError raised", "overlap" in str(e))

    def waived(st):
        with analysis.waiver("last-writer-wins is intended here"):
            st = aliasing(st)
        return st

    rep = lint(gas, waived, "vectored-alias-waived")
    check("R4 waiver downgrades raise to waived finding",
          rep.ok and len(rep.waived) == 1 and rep.waived[0].rule == "R4",
          f"(findings: {[f.render() for f in rep.findings]})")


# --------------------------------------------------------------------------
# registry entry points must all be clean (pass 1; pass 2 runs in CLI/CI)
# --------------------------------------------------------------------------

def test_registry_entries_clean():
    for name in registry.names():
        rep = registry.run_entry(name, include_hlo=False)
        check(f"entry {name} lints clean", rep.ok,
              f"({rep.n_events} events, {rep.tags_recovered} tags; "
              f"findings: {[f.render() for f in rep.findings]})")
        if name != "moe-dispatch":     # moe uses no shoal ops directly
            check(f"entry {name} tags recoverable from jaxpr",
                  rep.tags_recovered > 0 and rep.n_events > 0)
        if name == "jacobi":           # layer scopes add no shoal.* tag
            check("entry jacobi recovers one tag an event",
                  rep.tags_recovered == rep.n_events,
                  f"({rep.n_events} events, {rep.tags_recovered} tags)")


# --------------------------------------------------------------------------
# satellite 2: host-side debug surface for ERR_WAIT_UNDERFLOW
# --------------------------------------------------------------------------

def test_wait_underflow_host_exception():
    ctx, gas = make()

    def prog(st):
        return ops.wait_replies(ctx, st, token=5, n=3)

    st = jax.jit(gas.spmd(prog))(gas.make_global_state())
    try:
        raise_on_error(st, where="comm_lint_checks")
        raise AssertionError("raise_on_error did not raise")
    except WaitUnderflowError as e:
        check("WaitUnderflowError names the offending token",
              e.tokens == (5,), f"(tokens={e.tokens})")

    def clean(st):
        st = ops.put_long(ctx, st, jnp.arange(4, dtype=jnp.float32), RING,
                          dst_addr=0, token=1)
        return ops.wait_replies(ctx, st, token=1, n=1)

    st = jax.jit(gas.spmd(clean))(gas.make_global_state())
    check("raise_on_error passes a clean state",
          raise_on_error(st) is st)

    # the same broken schedule is caught statically, before any run
    try:
        jaxpr_lint.lint_clean(gas.spmd(prog), gas.make_global_state())
        raise AssertionError("lint_clean did not raise")
    except analysis.CommLintError as e:
        check("lint_clean raises CommLintError on the same schedule",
              "R3" in str(e))


def main():
    test_r1_strided_prefix_race()
    test_r1_unordered_write_pair()
    test_r2_get_vs_inflight_put()
    test_r3_credit_flow()
    test_r4_oob_and_vectored_alias()
    test_registry_entries_clean()
    test_wait_underflow_host_exception()
    print("COMM_LINT_CHECKS_ALL_PASS")


if __name__ == "__main__":
    main()
