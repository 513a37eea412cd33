"""The repro.policies layer: registry + PolicySet contract, the new
policy implementations (nextline/bestoffset prefetch, strict scheduling,
random/srrip replacement, static-rate adaptation), the planner's
policy-tag compile keys (same-tag policies fuse, numeric params never
split a group), and the non-negotiable default-policy invariant: the
default PolicySet executes the same program the SimFlags path always
did — bit for bit, through both the classic builders and the
experiments executor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FamConfig, fam_replace
from repro.core import dram_cache as dc
from repro.core.fam_params import FamParams, stack_params
from repro.core.famsim import SimFlags, build_sim, simulate, sweep
from repro.experiments import (Experiment, execute, flag_axis, plan_points,
                               policy_axis, workload_axis)
from repro.policies import (DEFAULT_POLICY_SET, POLICY_KINDS, PolicySet,
                            available, get_policy)

CFG = FamConfig()
DRAM = SimFlags()


# ---------------------------------------------------------------------------
# registry + PolicySet contract
# ---------------------------------------------------------------------------

def test_registry_has_the_policy_zoo():
    assert set(available("prefetch")) >= {"spp", "nextline", "bestoffset"}
    assert set(available("scheduler")) >= {"fifo", "wfq", "strict"}
    assert set(available("replacement")) >= {"lru", "random", "srrip"}
    assert set(available("adaptation")) >= {"token_bucket", "static"}
    with pytest.raises(KeyError, match="available"):
        get_policy("scheduler", "edf")


def test_policyset_tags_and_fusion():
    """fifo and wfq share the fused chain program (one compile tag); a
    different scheduler/prefetcher is a different tag."""
    assert PolicySet().compile_tags() == \
        PolicySet(scheduler="wfq").compile_tags()
    assert PolicySet(scheduler="strict").compile_tags() != \
        PolicySet().compile_tags()
    assert PolicySet(prefetch="nextline").compile_tags() != \
        PolicySet().compile_tags()
    # hashable (rides on ResolvedPoint / cache keys / dataclass defaults)
    assert hash(PolicySet().override("scheduler", weight=3.0)) == \
        hash(PolicySet().override("scheduler", weight=3.0))


def test_policyset_from_flags_mapping():
    """The SimFlags deprecation shim: wfq=True selects the wfq scheduler
    with the flag weight as a numeric-param override."""
    ps = PolicySet.from_flags(SimFlags(wfq=True, wfq_weight=3))
    assert ps.scheduler == "wfq"
    assert dict(dict(ps.overrides)["scheduler"])["weight"] == 3.0
    assert PolicySet.from_flags(SimFlags()).scheduler == "fifo"
    assert PolicySet.from_flags(None) == PolicySet.from_flags(SimFlags())


def test_numeric_params_schema_and_override_validation():
    ps = PolicySet()
    pol = ps.numeric_params(CFG)
    assert set(pol) == set(POLICY_KINDS)
    assert float(pol["prefetch"]["confidence_threshold"]) == \
        CFG.spp_confidence_threshold
    assert float(pol["scheduler"]["weight"]) == CFG.wfq_weight
    assert int(pol["adaptation"]["sample_interval"]) == CFG.sample_interval
    with pytest.raises(ValueError, match="no numeric param"):
        ps.override("scheduler", nope=1.0).numeric_params(CFG)
    with pytest.raises(ValueError, match="unknown policy kind"):
        ps.override("queueing", weight=1.0)


def test_override_validates_eagerly_with_schema_listing():
    """A typo'd knob raises AT THE OVERRIDE CALL (not when numeric_params
    eventually runs — or never, for a caller that only serializes the
    set), and the error lists the valid keys; the schema follows the
    CHOSEN policy."""
    with pytest.raises(ValueError) as e:
        PolicySet().override("scheduler", wieght=3.0)
    assert "wieght" in str(e.value)
    for valid in ("weight", "backlog_cap", "use_wfq"):
        assert valid in str(e.value)
    assert tuple(PolicySet().param_schema("prefetch")) == \
        ("confidence_threshold",)
    # the strict scheduler has backlog_cap but no weight
    strict = PolicySet(scheduler="strict")
    strict.override("scheduler", backlog_cap=800.0)
    with pytest.raises(ValueError, match="no numeric param"):
        strict.override("scheduler", weight=1.0)


def test_policyset_dict_round_trip():
    """as_dict/from_dict is the search layer's candidate serialization:
    exact round-trip, JSON-able, and re-validating on the way in."""
    import json
    ps = PolicySet(scheduler="wfq").override(
        "scheduler", weight=3.0).override("prefetch",
                                          confidence_threshold=0.4)
    d = json.loads(json.dumps(ps.as_dict()))
    assert PolicySet.from_dict(d) == ps
    assert PolicySet.from_dict(PolicySet().as_dict()) == PolicySet()
    with pytest.raises(ValueError, match="unknown keys"):
        PolicySet.from_dict({"sched": "wfq"})
    with pytest.raises(ValueError, match="no numeric param"):
        PolicySet.from_dict({"overrides": {"scheduler": {"nope": 1.0}}})


def test_famparams_carries_policy_pytree():
    """Policy numeric params are ordinary traced leaves: stack/vmap-able,
    and with_flags maps the legacy wfq booleans onto the chain
    scheduler's params."""
    p = FamParams.of(CFG, SimFlags(wfq=True, wfq_weight=3))
    assert bool(p.policy["scheduler"]["use_wfq"])
    assert float(p.policy["scheduler"]["weight"]) == 3.0
    batch = stack_params([p, FamParams.of(CFG)])
    assert batch.policy["scheduler"]["weight"].shape == (2,)
    flipped = batch.with_flags(SimFlags(wfq=False, wfq_weight=1))
    assert not np.asarray(flipped.policy["scheduler"]["use_wfq"]).any()
    np.testing.assert_array_equal(
        np.asarray(flipped.policy["scheduler"]["weight"]), [1.0, 1.0])


#: members of one compile group each (equal policy compile tags), mixing
#: what the FamParams leaves carry: block sizes, feature flags, a WFQ
#: weight, numeric-param overrides, and floats float32 must round
_PARAM_GROUPS = {
    "block_sizes": [(fam_replace(CFG, block_bytes=b), DRAM, None)
                    for b in (64, 256, 4096)],
    "flags": [(CFG, fl, None) for fl in (
        SimFlags(core_prefetch=False, dram_prefetch=False),
        SimFlags(bw_adapt=True), SimFlags(all_local=True))],
    "wfq_weight": [(CFG, SimFlags(wfq=True, wfq_weight=w), None)
                   for w in (1, 3, 0.7)],
    "override": [
        (CFG, DRAM, PolicySet().override("prefetch",
                                         confidence_threshold=0.37)),
        (CFG, DRAM, PolicySet().override("adaptation", ema_alpha=0.1,
                                         sample_interval=300))],
    "mixed": [
        (fam_replace(CFG, block_bytes=128, mimd_increase=1.1),
         SimFlags(wfq=True, wfq_weight=2.5, bw_adapt=True), None),
        (CFG, SimFlags(core_prefetch=False),
         PolicySet(scheduler="wfq").override("scheduler", weight=0.3)),
        (fam_replace(CFG, block_bytes=4096), DRAM,
         PolicySet().override("prefetch", confidence_threshold=0.9))],
}


def _jnp_stacked(monkeypatch, members):
    """Reference: the same members built from eager ``jnp`` scalars (the
    constructors the leaves had before they moved to the host) and
    stacked leaf by leaf with ``jnp.stack``."""
    import types

    from repro.core import fam_params
    from repro.policies import adaptation, base, prefetch, scheduler
    as_jnp = types.SimpleNamespace(float32=jnp.float32, int32=jnp.int32,
                                   bool_=jnp.bool_, asarray=jnp.asarray)
    with monkeypatch.context() as m:
        for mod in (fam_params, adaptation, base, prefetch, scheduler):
            m.setattr(mod, "np", as_jnp)
        per = [FamParams.of(c, fl, pol) for c, fl, pol in members]
    assert all(isinstance(x, jax.Array)
               for p in per for x in jax.tree.leaves(p))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


@pytest.mark.parametrize("group", sorted(_PARAM_GROUPS))
def test_host_params_bit_identical_to_jnp_scalars(monkeypatch, group):
    """FamParams.of builds host numpy leaves (no device dispatch per
    scalar), and stack_params of them equals a jnp-scalar stack bit for
    bit and in dtype."""
    members = _PARAM_GROUPS[group]
    per = [FamParams.of(c, fl, pol) for c, fl, pol in members]
    for p in per:
        for leaf in jax.tree.leaves(p):
            assert isinstance(leaf, (np.generic, np.ndarray)), type(leaf)
            assert not isinstance(leaf, jax.Array)
    got = stack_params(per)
    ref = _jnp_stacked(monkeypatch, members)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert isinstance(g, np.ndarray) and g.shape == (len(members),)
        assert g.dtype == r.dtype
        assert g.tobytes() == np.asarray(r).tobytes()


def test_hoisted_core_constants_in_static_key():
    """The former famsim module constants are FamConfig shape fields now
    and participate in the compile key (defaults unchanged)."""
    assert (CFG.core_pf_degree, CFG.completions_per_step,
            CFG.core_fill_entries) == (2, 8, 64)
    assert fam_replace(CFG, core_pf_degree=4).geometry_free_shape() != \
        CFG.geometry_free_shape()
    assert fam_replace(CFG, core_fill_entries=128).geometry_free_shape() != \
        CFG.geometry_free_shape()


# ---------------------------------------------------------------------------
# prefetch policies
# ---------------------------------------------------------------------------

def test_nextline_predicts_sequential_blocks():
    nl = get_policy("prefetch", "nextline")
    pol = nl.params_of(CFG)
    blocks, valid = nl.predict(CFG, pol, nl.init(CFG), jnp.int32(7),
                               jnp.int32(10), jnp.int32(0), 4, 64)
    np.testing.assert_array_equal(np.asarray(blocks), 7 * 64 + 10 +
                                  np.arange(1, 5))
    assert np.asarray(valid).all()
    # page-boundary clip
    _, valid = nl.predict(CFG, pol, nl.init(CFG), jnp.int32(7),
                          jnp.int32(62), jnp.int32(0), 4, 64)
    np.testing.assert_array_equal(np.asarray(valid), [True, False, False,
                                                      False])


def test_bestoffset_learns_a_constant_stride():
    bo = get_policy("prefetch", "bestoffset")
    pol = dict(bo.params_of(CFG))
    pol["round_len"] = jnp.float32(16.0)
    pol["score_threshold"] = jnp.float32(4.0)
    state = bo.init(CFG)
    for b in range(0, 40, 2):                    # in-page stride-2 stream
        state, _ = bo.train(CFG, pol, state, jnp.int32(5),
                            jnp.int32(b % 64), jnp.bool_(True))
    assert int(state.best) == 2
    blocks, valid = bo.predict(CFG, pol, state, jnp.int32(5), jnp.int32(10),
                               jnp.int32(0), 4, 64)
    np.testing.assert_array_equal(np.asarray(blocks)[np.asarray(valid)],
                                  5 * 64 + np.array([12, 14, 16, 18]))


def test_bestoffset_stays_disabled_below_threshold():
    bo = get_policy("prefetch", "bestoffset")
    pol = dict(bo.params_of(CFG))
    pol["round_len"] = jnp.float32(8.0)
    state = bo.init(CFG)
    rng = np.random.default_rng(0)
    for b in rng.integers(0, 64, 20):            # patternless stream
        state, _ = bo.train(CFG, pol, state, jnp.int32(5), jnp.int32(int(b)),
                            jnp.bool_(True))
    _, valid = bo.predict(CFG, pol, state, jnp.int32(5), jnp.int32(10),
                          jnp.int32(0), 4, 64)
    assert not np.asarray(valid).any()           # "no prefetch > bad prefetch"


# ---------------------------------------------------------------------------
# scheduler policies
# ---------------------------------------------------------------------------

def _arb_inputs():
    d_arr = jnp.float32([100.0])
    d_valid = jnp.bool_([True])
    d_bytes = jnp.float32([64.0])
    p_arr = jnp.zeros((4,), jnp.float32)         # prefetches arrived FIRST
    p_valid = jnp.ones((4,), jnp.bool_)
    p_bytes = jnp.full((4,), 4096.0, jnp.float32)
    return d_arr, d_valid, d_bytes, p_arr, p_valid, p_bytes


def test_strict_scheduler_shields_demands_from_prefetch_backlog():
    """Under strict priority a demand's finish time is independent of the
    queued prefetches (FIFO makes it wait behind them)."""
    p = FamParams.of(CFG, policies=PolicySet(scheduler="strict"))
    strict = get_policy("scheduler", "strict")
    pol = strict.params_of(CFG)
    busy0 = jnp.zeros((2,), jnp.float32)
    d_arr, d_valid, d_bytes, p_arr, p_valid, p_bytes = _arb_inputs()
    t = strict.arbitrate(p, pol, busy0, d_arr, d_valid, d_bytes,
                         p_arr, p_valid, p_bytes)
    lat_fixed = p.fam_mem_latency + p.cxl_min_latency_cycles
    unloaded = 100.0 + float(p.fam_service_cycles(64.0) + lat_fixed)
    assert float(t.demand_finish[0]) == pytest.approx(unloaded)

    fifo = get_policy("scheduler", "fifo")
    t_fifo = fifo.arbitrate(p, fifo.params_of(CFG), busy0, d_arr, d_valid,
                            d_bytes, p_arr, p_valid, p_bytes)
    assert float(t_fifo.demand_finish[0]) > float(t.demand_finish[0])
    # prefetches defer to the demand drain point under strict
    assert float(jnp.min(jnp.where(p_valid, t.prefetch_finish, jnp.inf))) > \
        float(t.demand_finish[0]) - lat_fixed


def test_strict_backlog_gate_always_applies():
    strict = get_policy("scheduler", "strict")
    pol = strict.params_of(CFG)
    p = FamParams.of(CFG, policies=PolicySet(scheduler="strict"))
    busy = jnp.float32([0.0, CFG.wfq_backlog_cap + 1.0])
    assert not bool(strict.backlog_ok(p, pol, busy, jnp.float32(0.0)))
    fifo = get_policy("scheduler", "fifo")
    # FIFO (use_wfq False) never gates
    assert bool(fifo.backlog_ok(FamParams.of(CFG), fifo.params_of(CFG),
                                busy, jnp.float32(0.0)))


# ---------------------------------------------------------------------------
# replacement policies
# ---------------------------------------------------------------------------

def _fill_set(policy, n_ways, blocks):
    st = dc.init_cache(1, n_ways)
    for b in blocks:
        st, _, _ = dc.insert(st, jnp.int32(b), policy=policy)
    return st


def test_random_replacement_deterministic_and_in_effective_ways():
    rnd = get_policy("replacement", "random").bind({})
    st = _fill_set(rnd, 4, [1, 2, 3, 4])
    st1, ev1, slot1 = dc.insert(st, jnp.int32(9), policy=rnd)
    st2, ev2, slot2 = dc.insert(st, jnp.int32(9), policy=rnd)
    assert int(ev1) in (1, 2, 3, 4)
    assert int(ev1) == int(ev2) and int(slot1) == int(slot2)  # replay-exact
    # padded state: victims stay inside the effective ways
    stp = dc.init_cache(1, 8)
    for b in (1, 2):
        stp, _, _ = dc.insert(stp, jnp.int32(b), ways=2, policy=rnd)
    for b in range(10, 30):
        stp, _, way = dc.insert(stp, jnp.int32(b), ways=2, policy=rnd)
        assert int(way) % 8 < 2
    assert (np.asarray(stp.tags)[:, 2:] == 0).all()


def test_srrip_evicts_distant_and_protects_rereferenced():
    srrip = get_policy("replacement", "srrip").bind({})
    st = _fill_set(srrip, 2, [1, 2])             # both inserted at RRPV 2
    hit, si, way = dc.lookup(st, jnp.int32(1))
    st = dc.touch(st, si, way, enable=hit, policy=srrip)   # 1 -> RRPV 0
    st, evicted, _ = dc.insert(st, jnp.int32(3), policy=srrip)
    assert int(evicted) == 2                     # aged to 3; 1 only to 1
    hit1, _, _ = dc.lookup(st, jnp.int32(1))
    assert bool(hit1)


def test_srrip_redundant_fill_promotes_not_demotes():
    """A duplicate fill of an already-present block is a re-reference:
    it must take the policy's hit update (RRPV -> 0), never the fresh
    insert value — otherwise a hot line becomes the next victim."""
    srrip = get_policy("replacement", "srrip").bind({})
    st = _fill_set(srrip, 2, [1, 2])
    hit, si, way = dc.lookup(st, jnp.int32(1))
    st = dc.touch(st, si, way, enable=hit, policy=srrip)   # 1 -> RRPV 0
    st, ev, _ = dc.insert(st, jnp.int32(1), policy=srrip)  # redundant fill
    assert int(ev) == -1
    st, evicted, _ = dc.insert(st, jnp.int32(3), policy=srrip)
    assert int(evicted) == 2                     # 1 stayed protected


def test_lru_policy_binds_to_classic_path():
    lru = get_policy("replacement", "lru")
    assert lru.bind({}) is None                  # dram_cache fast path


# ---------------------------------------------------------------------------
# adaptation policies
# ---------------------------------------------------------------------------

def test_static_rate_pins_the_issue_rate():
    ps = PolicySet(adaptation="static").override("adaptation", rate=0.02)
    out = simulate(CFG, SimFlags(bw_adapt=True), ["603.bwaves_s"], T=2000,
                   policies=ps)
    np.testing.assert_allclose(out["issue_rate"], 0.02)
    full = simulate(CFG, SimFlags(bw_adapt=True), ["603.bwaves_s"], T=2000,
                    policies=PolicySet(adaptation="static"))
    np.testing.assert_allclose(full["issue_rate"], 1.0)
    # a binding rate issues measurably fewer prefetches (the bucket refills
    # at 0.02 tokens/event against a streaming demand for ~4 per event)
    assert out["prefetches_issued"].sum() < 0.5 * \
        full["prefetches_issued"].sum()


def test_static_rate_active_without_bw_adapt_flag():
    """The policy owns its activation gate: an explicitly chosen static
    policy limits prefetch issue even when the legacy bw_adapt flag is
    off (the flag only selects the token bucket's on/off comparison)."""
    ps = PolicySet(adaptation="static").override("adaptation", rate=0.02)
    limited = simulate(CFG, SimFlags(), ["603.bwaves_s"], T=2000,
                       policies=ps)
    unlimited = simulate(CFG, SimFlags(), ["603.bwaves_s"], T=2000)
    assert limited["prefetches_issued"].sum() < 0.5 * \
        unlimited["prefetches_issued"].sum()
    # while the token bucket stays flag-gated: bw_adapt=False == no-op
    np.testing.assert_allclose(unlimited["issue_rate"], 1.0)


def test_policy_matrix_baseline_requires_exact_default():
    """An overridden look-alike must never be picked as the matrix
    baseline (full-dataclass equality, overrides included)."""
    from benchmarks.fig12_wfq import _baseline_label
    capped = PolicySet().override("scheduler", backlog_cap=500.0)
    assert _baseline_label({"capped": capped, "base": PolicySet()}) == "base"
    with pytest.raises(ValueError, match="baseline"):
        _baseline_label({"capped": capped})


def test_all_new_policies_end_to_end_sane():
    """A maximally non-default PolicySet still satisfies the simulator's
    counter invariants."""
    ps = PolicySet(prefetch="bestoffset", scheduler="strict",
                   replacement="srrip", adaptation="static")
    out = simulate(CFG, SimFlags(bw_adapt=True), ["bfs", "mg"], T=3000,
                   policies=ps)
    assert np.isfinite(out["ipc"]).all() and (out["ipc"] > 0).all()
    assert (out["demand_hit_fraction"] >= 0).all()
    assert (out["demand_hit_fraction"] <= 1).all()
    assert (out["prefetches_issued"] >= 0).all()


# ---------------------------------------------------------------------------
# planner: policy tags in the compile key
# ---------------------------------------------------------------------------

def test_policy_axis_groups_by_compile_tag():
    """fifo/wfq/any-weight fuse into one group; strict and nextline each
    split (different traced programs); numeric-param overrides never
    split."""
    exp = Experiment(
        name="ptags", T=600, workloads=("LU",),
        axes=(policy_axis({
            "fifo": PolicySet(),
            "wfq": PolicySet(scheduler="wfq"),
            "w3": PolicySet(scheduler="wfq").override("scheduler",
                                                      weight=3.0),
            "strict": PolicySet(scheduler="strict"),
            "nextline": PolicySet(prefetch="nextline"),
        }),))
    plan = exp.plan()
    assert plan.num_groups == 3
    assert plan.groups[0].indices == (0, 1, 2)   # the fused chain family
    tags = [g.key.static_shape[-4:] for g in plan.groups]
    assert len(set(tags)) == 3


def test_wfq_weight_sweep_shares_one_compile_group():
    """The satellite regression: the WFQ weight lives on the scheduler
    policy's numeric params, so a weight sweep is ONE group (and so is
    the legacy flag spelling)."""
    weights = policy_axis({f"w{w}": PolicySet(scheduler="wfq").override(
        "scheduler", weight=float(w)) for w in (1, 2, 3, 4)})
    plan = Experiment(name="wsweep", T=600, workloads=("LU",),
                      axes=(weights,)).plan()
    assert plan.num_groups == 1
    legacy = Experiment(
        name="wflags", T=600, workloads=("LU",),
        axes=(flag_axis("v", {f"w{w}": SimFlags(wfq=True, wfq_weight=w)
                              for w in (1, 2, 3)}),))
    assert legacy.plan().num_groups == 1


def test_fig12_policy_matrix_plans_chain_fusion():
    from benchmarks.fig12_wfq import policy_experiment
    from benchmarks.run import policy_combos
    combos = policy_combos(["scheduler=fifo,wfq,strict",
                            "prefetch=spp,nextline"], pytest.fail)
    assert set(combos) == {"spp+fifo", "spp+wfq", "spp+strict",
                           "nextline+fifo", "nextline+wfq",
                           "nextline+strict"}
    plan = policy_experiment(combos, quick=True).plan()
    # per node count: {fifo,wfq}xspp fuse, strict x spp, {fifo,wfq} x
    # nextline, strict x nextline -> 4 tag-combos x 2 node counts
    assert plan.num_groups == 8


# ---------------------------------------------------------------------------
# the default-policy invariant (bit-exactness)
# ---------------------------------------------------------------------------

def test_default_policy_set_matches_flags_path_bit_exact():
    """An explicit default PolicySet and the legacy SimFlags spelling must
    produce byte-identical metrics through the classic sweep path."""
    from repro.core.traces import generate, node_seed
    a, g = generate("LU", 800, node_seed(0, 0))
    addrs, gaps = a[None], g[None]
    flag_sets = [SimFlags(), SimFlags(wfq=True, wfq_weight=3),
                 SimFlags(bw_adapt=True)]
    params = stack_params([FamParams.of(CFG, fl) for fl in flag_sets])
    ref = sweep(CFG, params, None, np.stack([addrs] * 3),
                np.stack([gaps] * 3))
    explicit = [FamParams.of(CFG, fl, PolicySet.from_flags(fl))
                for fl in flag_sets]
    got = sweep(CFG, stack_params(explicit), None, np.stack([addrs] * 3),
                np.stack([gaps] * 3), policies=DEFAULT_POLICY_SET)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(got[k]), err_msg=k)


def test_policy_axis_default_combo_matches_flag_axis_bit_exact():
    """Through the experiments executor: a policy_axis selecting the
    default set reproduces the flag-axis run bit-for-bit (same compile
    group key, same traces, same program)."""
    T = 700
    by_flags = Experiment(
        name="pflags", T=T, workloads=("LU", "bfs"),
        axes=(flag_axis("v", {"dram": DRAM}),)).run()
    by_policy = Experiment(
        name="ppol", T=T, workloads=("LU", "bfs"), flags=DRAM,
        axes=(policy_axis({"default": PolicySet()}),)).run()
    ref = by_flags.get(v="dram")
    got = by_policy.get(policy="default")
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)


def test_new_policy_combo_through_executor():
    """A non-default combo runs end-to-end through plan/execute and lands
    in its own compile group, reproducing the classic build_sim path for
    the same PolicySet bit-exactly (pre-staged device traces)."""
    from repro.traces.device import system_traces as dev_traces
    ps = PolicySet(prefetch="nextline", scheduler="strict",
                   replacement="random")
    T = 600
    exp = Experiment(name="combo", T=T, workloads=("LU",), flags=DRAM,
                     axes=(policy_axis({"combo": ps}),))
    plan = exp.plan()
    assert plan.num_groups == 1
    res = execute(plan)
    a, g = dev_traces(["LU"], T, 0)
    run = build_sim(CFG, DRAM, 1, policies=ps)
    ref = run(jnp.asarray(a), jnp.asarray(g))
    got = res.get(policy="combo")
    for k, v in ref.items():
        np.testing.assert_array_equal(np.asarray(v), got[k], err_msg=k)


def test_wfq_fairness_bound_64_distinct_weights():
    """The deficit-round-robin fairness-gap bound of
    ``test_schedule_batch_deficit_round_robin_fairness`` must survive the
    multi-tenant regime: >= 64 DISTINCT per-tenant weights riding one
    traced weight input through one jitted executable (exactly how
    repro.tenants lowers a fleet's WFQ entitlements — weight is a vmap
    lane, never a compile key). For every weight w, consecutive
    prefetch-grant gaps stay <= 2*(w+1) and prefetch never starves."""
    from repro.core import wfq

    max_issues = 256

    def drain(w):
        _, order = wfq.schedule_batch(
            wfq.init_wfq(), jnp.int32(512), jnp.int32(512),
            weight=w, max_issues=max_issues)
        return order

    weights = jnp.arange(1, 65, dtype=jnp.int32)      # 64 distinct weights
    orders = np.asarray(jax.jit(jax.vmap(drain))(weights))
    assert orders.shape == (64, max_issues)
    for w, order in zip(np.asarray(weights), orders):
        assert not np.any(order == wfq.IDLE)          # saturated backlog
        pf = np.flatnonzero(order == wfq.PREFETCH)
        bound = 2 * (int(w) + 1)
        # no starvation: at least the DRR floor of prefetch grants
        assert len(pf) >= max(1, max_issues // bound - 1), int(w)
        # first grant arrives within one full demand quantum
        assert pf[0] <= bound, int(w)
        if len(pf) > 1:
            assert int(np.diff(pf).max()) <= bound, int(w)
