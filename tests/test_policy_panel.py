"""Cross-policy exactness tier for the device policy panel (ISSUE 9).

The fused step's admission/victim rules are now an enum
(``StepSpec.policy``: wtinylfu | s3fifo | arc | lfu) dispatched statically
over the shared set-associative machinery.  This tier pins the panel four
ways:

1. **Exactness** — each competitor's device hit sequence equals its host
   twin (``core.policies.SetAssoc*``) bit-for-bit: s3fifo/lfu under
   collision-free sketches (huge width, doorkeeper off, so both hash
   families degenerate to exact counts), arc exact-by-construction at any
   ``dk_bits`` (the twin replays the device's ghost-Bloom arithmetic
   through ``dk_probe_index_np``).
2. **Program pin** — ``policy="wtinylfu"`` lowers the byte-identical HLO
   as a spec that never mentions policy (the same exactness-ladder pin as
   shards=1/adaptive=False): the panel refactor cannot perturb the default
   engine.
3. **Goldens** — per-policy hit ratios on the golden zipf and
   scan-then-hotspot traces, pinned to ±0.01.
4. **Ordering** — W-TinyLFU >= every competitor on the golden Zipf trace
   at the paper's sizing (the panel exists to make this claim testable).

Plus the satellite regressions: ``simulate_sweep`` row-schema round-trip
(rows used to omit ``streams``/``integrity``/``merge_every``) and
policy-parameterized property tests (capacity bound, hits never evict,
lane isolation) under the optional-hypothesis shim.
"""
import numpy as np
import jax
import pytest

from _hypothesis_compat import given, settings, strategies as st

from repro.core.device_simulate import (DeviceWTinyLFU, _jit_step,
                                        _row_extra, _trace_lanes,
                                        simulate_trace, simulate_sweep)
from repro.core.policies import SetAssocARC, SetAssocLFU, SetAssocS3FIFO
from repro.core.wtinylfu import WTinyLFU
from repro.kernels.sketch_common import POLICIES
from repro.kernels.sketch_step import (StepSpec, _EMPTY, _I32_MAX,
                                       _RECORD_BLOCK, MT_LO,
                                       MT_HI, MT_META, R_SIZE, WT_META,
                                       init_step_state, step_ref)
from repro.traces import panel_traces, zipf_trace
from repro.traces.synthetic import zipf_probs, _sample_from_probs

COMPETITORS = ("s3fifo", "arc", "lfu")

# ---------------------------------------------------------------------------
# pinned goldens (trace construction + configs below must not change).
# Measured on the jit scan; the tolerance is the cross-refactor acceptance
# band, an order of magnitude above float/jitter (the runs are integer-
# deterministic) and far below any behavioral regression.
# ---------------------------------------------------------------------------
GOLDEN_TOL = 0.01
# golden zipf (C=200, warmup=10k, assoc=8, sample_factor=8)
GOLDEN_ZIPF = {"wtinylfu": 0.3407, "s3fifo": 0.3470,
               "arc": 0.3517, "lfu": 0.2699}
# scan-then-hotspot (C=400, warmup=5k, assoc=8, sample_factor=8)
GOLDEN_SCANHOT = {"wtinylfu": 0.4800, "s3fifo": 0.4790,
                  "arc": 0.4786, "lfu": 0.4650}


def _wf(policy: str) -> float:
    """Per-policy window_frac: s3fifo gets the S3-FIFO paper's 10% small
    queue; arc/lfu ignore the knob (window pinned to its 1-slot minimum)."""
    return 0.1 if policy == "s3fifo" else 0.01


def golden_zipf_trace():
    return zipf_trace(60_000, n_items=50_000, alpha=0.9, seed=7)


def scan_then_hotspot_trace():
    rng = np.random.default_rng(13)
    scan = np.arange(100_000, 125_000, dtype=np.int64)
    hot = _sample_from_probs(zipf_probs(2_000, 1.0), 35_000,
                             rng).astype(np.int64)
    return np.concatenate([scan, hot])


# ===========================================================================
# 1. device == host-twin hit sequence, bit for bit
# ===========================================================================

def _device_hits(cfg: DeviceWTinyLFU, trace: np.ndarray) -> np.ndarray:
    _, _, hits = simulate_trace(trace, cfg.capacity, return_state=True,
                                **{f: getattr(cfg, f) for f in
                                   ("window_frac", "sample_factor",
                                    "counters_per_item", "doorkeeper",
                                    "dk_bits_per_item", "assoc", "policy")})
    return np.asarray(hits)


class TestDeviceTwinParity:
    """Per-access hit-sequence parity on a 5k-access zipf trace whose
    working set churns a C=60 cache hard (every structural rule — FIFO
    order, CLOCK marks, ghost adaptation, min-frequency victims — is
    exercised thousands of times; one divergent access fails the test)."""

    C = 60
    TRACE = zipf_trace(5_000, n_items=600, alpha=0.9, seed=11)

    # collision-free sketch recipe shared by the sketch-consulting twins:
    # ~550 counters/item makes both hash families exact counters, and
    # doorkeeper=False removes the only cross-family +1 disagreement
    FREE = dict(sample_factor=8, counters_per_item=550.0, doorkeeper=False)

    def _twin_hits(self, twin) -> np.ndarray:
        return np.array([twin.access(int(k)) for k in self.TRACE], np.int32)

    def test_s3fifo_bit_for_bit(self):
        cfg = DeviceWTinyLFU(self.C, assoc=8, policy="s3fifo",
                             window_frac=0.1, **self.FREE)
        twin = SetAssocS3FIFO(self.C, window_frac=0.1, assoc=8, **self.FREE)
        dev = _device_hits(cfg, self.TRACE)
        assert np.array_equal(dev, self._twin_hits(twin))

    def test_lfu_bit_for_bit(self):
        cfg = DeviceWTinyLFU(self.C, assoc=8, policy="lfu", **self.FREE)
        twin = SetAssocLFU(self.C, assoc=8, **self.FREE)
        dev = _device_hits(cfg, self.TRACE)
        assert np.array_equal(dev, self._twin_hits(twin))

    def test_arc_bit_for_bit_at_realistic_dk_bits(self):
        """ARC parity needs NO collision-free assumption: the twin replays
        the device Bloom-ghost arithmetic, so even a deliberately tiny
        (collision-heavy) filter must agree bit-for-bit."""
        cfg = DeviceWTinyLFU(self.C, assoc=8, policy="arc")
        twin = SetAssocARC(self.C, assoc=8, dk_bits=cfg.dk_bits, dk_probes=3)
        dev = _device_hits(cfg, self.TRACE)
        assert np.array_equal(dev, self._twin_hits(twin))

    def test_arc_bit_for_bit_at_tiny_dk_bits(self):
        spec_bits = 256                    # ~4 bits/ghost: heavy aliasing
        cfg = DeviceWTinyLFU(self.C, assoc=8, policy="arc",
                             dk_bits_per_item=spec_bits / (8 * self.C))
        assert cfg.dk_bits == spec_bits
        twin = SetAssocARC(self.C, assoc=8, dk_bits=spec_bits, dk_probes=3)
        dev = _device_hits(cfg, self.TRACE)
        assert np.array_equal(dev, self._twin_hits(twin))

    @pytest.mark.parametrize("streams", [1, 4])
    @pytest.mark.parametrize("policy", POLICIES)
    def test_pallas_equals_scan_equals_twin(self, policy, streams):
        """Every set-path policy builds its table records from the packed
        access record: the fused kernel (fed the records) equals the scan
        (records scanned at streams=1, built per access under lanes) in
        state and hit flags, and each lane's hits equal its host twin's,
        over 1,500 accesses that cross the §3.3 reset (W = 8 C = 480)."""
        n = 1_500
        traces = np.stack([zipf_trace(n, n_items=600, alpha=0.9, seed=s)
                           for s in range(21, 21 + streams)])
        free = {} if policy == "arc" else self.FREE
        kw = dict(assoc=8, policy=policy, window_frac=_wf(policy), **free)
        twins = {"wtinylfu": lambda: WTinyLFU(self.C, window_frac=0.01,
                                              assoc=8, **free),
                 "s3fifo": lambda: SetAssocS3FIFO(self.C, window_frac=0.1,
                                                  assoc=8, **free),
                 "lfu": lambda: SetAssocLFU(self.C, assoc=8, **free),
                 "arc": lambda: SetAssocARC(
                     self.C, assoc=8, dk_probes=3,
                     dk_bits=DeviceWTinyLFU(self.C, **kw).dk_bits)}
        run = {}
        for backend in ("jit", "pallas"):
            _, state, hits = simulate_trace(
                traces if streams > 1 else traces[0], self.C,
                streams=streams, backend=backend, chunk=512,
                return_state=True, **kw)
            run[backend] = (state, np.asarray(hits).reshape(streams, n))
        (s_ref, h_ref), (s_pal, h_pal) = run["jit"], run["pallas"]
        for k in s_ref:
            np.testing.assert_array_equal(np.asarray(s_ref[k]),
                                          np.asarray(s_pal[k]),
                                          err_msg=f"state[{k!r}]")
        np.testing.assert_array_equal(h_ref, h_pal)
        if policy != "arc":                 # the sketch policies reset
            sizes = np.asarray(s_ref["regs"]).reshape(streams, -1)[:, R_SIZE]
            assert (sizes < n).all()
        for b in range(streams):
            twin = twins[policy]()
            np.testing.assert_array_equal(
                h_ref[b], [twin.access(int(k)) for k in traces[b]],
                err_msg=f"lane {b} vs host twin")

    @pytest.mark.parametrize("streams, masked", [(1, False), (2, True)])
    @pytest.mark.parametrize("policy", POLICIES)
    def test_record_blocks_equal_one_block_chunks(self, policy, streams,
                                                  masked):
        """A step over two whole record blocks and a tail (the block loop
        and the tail scan) equals the same accesses stepped one block per
        call, in final state and hit flags; with ``n_valid`` masking the
        last 77 accesses too, where a masked chunk leaves its state as
        it found it."""
        n = 2 * _RECORD_BLOCK + 300
        nv = n - 77 if masked else None
        traces = np.stack([zipf_trace(n, n_items=600, alpha=0.9, seed=s)
                           for s in range(31, 31 + streams)])
        free = {} if policy == "arc" else self.FREE
        cfg = DeviceWTinyLFU(self.C, assoc=8, policy=policy, streams=streams,
                             window_frac=_wf(policy), **free)
        spec = cfg.spec()
        state = init_step_state(spec, cfg.window_cap, cfg.main_cap)
        lo, hi = _trace_lanes(traces if streams > 1 else traces[0])
        whole, hits = _jit_step(spec, cfg.params(), state, lo, hi, nv)
        parts = []
        for a in range(0, n, _RECORD_BLOCK):
            b = min(a + _RECORD_BLOCK, n)
            part_nv = None if nv is None else max(0, min(b, nv) - a)
            state, h = _jit_step(spec, cfg.params(), state, lo[..., a:b],
                                 hi[..., a:b], part_nv)
            parts.append(np.asarray(h))
        np.testing.assert_array_equal(np.asarray(hits),
                                      np.concatenate(parts, axis=-1))
        for k in whole:
            np.testing.assert_array_equal(np.asarray(whole[k]),
                                          np.asarray(state[k]),
                                          err_msg=f"state[{k!r}]")


# ===========================================================================
# 2. policy="wtinylfu" compiles the byte-identical program
# ===========================================================================

def test_wtinylfu_policy_is_the_identical_program():
    """The panel dispatch is static: the default policy must lower to the
    byte-identical HLO as a spec that predates the enum — the exactness
    ladder's 'the refactor cannot have perturbed the default engine'
    guarantee, enforced through the central fingerprint registry (R7)."""
    from repro.analysis.program_lint import assert_identical_program
    assert_identical_program("policy-default")


def test_competitor_specs_validate_eagerly():
    for pol in COMPETITORS:
        with pytest.raises(ValueError):
            DeviceWTinyLFU(100, policy=pol)            # needs assoc
    with pytest.raises(ValueError):
        DeviceWTinyLFU(100, policy="arc", assoc=8, doorkeeper=False)
    with pytest.raises(ValueError):
        DeviceWTinyLFU(100, policy="s3fifo", assoc=8, shards=2)
    with pytest.raises(ValueError):
        DeviceWTinyLFU(100, policy="lfu", assoc=8, adaptive=True)
    with pytest.raises(ValueError):
        DeviceWTinyLFU(100, policy="bogus")
    with pytest.raises(AssertionError):
        StepSpec(width=256, rows=4, dk_bits=0, window_slots=8,
                 main_slots=64, assoc=8, policy="arc")  # arc needs dk_bits


# ===========================================================================
# 3 + 4. golden hit ratios, and W-TinyLFU wins the golden Zipf
# ===========================================================================

class TestGoldenPanel:
    def _panel(self, trace, C, warmup, **kw):
        return {pol: simulate_trace(trace, C, assoc=8, policy=pol,
                                    window_frac=_wf(pol), warmup=warmup,
                                    **kw).hit_ratio
                for pol in POLICIES}

    def test_golden_zipf_panel(self):
        got = self._panel(golden_zipf_trace(), 200, 10_000)
        for pol, want in GOLDEN_ZIPF.items():
            assert abs(got[pol] - want) < GOLDEN_TOL, (pol, got[pol], want)

    def test_golden_scanhot_panel(self):
        got = self._panel(scan_then_hotspot_trace(), 400, 5_000)
        for pol, want in GOLDEN_SCANHOT.items():
            assert abs(got[pol] - want) < GOLDEN_TOL, (pol, got[pol], want)

    def test_wtinylfu_beats_every_competitor_on_golden_zipf(self):
        """The paper's claim, now falsifiable in-repo: at the paper's
        sketch sizing (sample_factor=16 needs byte counters — the 4-bit
        cap at sf=8 blunts W-TinyLFU's frequency resolution more than its
        competitors') W-TinyLFU's hit ratio is >= every panel policy on
        the golden Zipf trace."""
        got = self._panel(golden_zipf_trace(), 1000, 10_000,
                          sample_factor=16, counter_bits=8)
        for pol in COMPETITORS:
            assert got["wtinylfu"] >= got[pol], (pol, got)


def test_panel_traces_families():
    fams = panel_traces(length=4_000, seed=3)
    assert set(fams) == {"zipf", "scan-hot", "churn", "loop"}
    for name, tr in fams.items():
        assert tr.dtype == np.int64 and len(tr) == 4_000, name
    # deterministic in seed
    again = panel_traces(length=4_000, seed=3)
    assert all(np.array_equal(fams[k], again[k]) for k in fams)


# ===========================================================================
# satellite: sweep row schema round-trips every config knob
# ===========================================================================

class TestSweepRowSchema:
    TR = zipf_trace(3_000, n_items=2_000, alpha=0.9, seed=5)

    def test_policy_axis_rows(self):
        rows = simulate_sweep(self.TR, [64], policies=POLICIES, assoc=8,
                              window_fracs=(0.1,))
        assert [r.policy for r in rows] == \
            ["w-tinylfu(device)", "s3fifo(device)", "arc(device)",
             "lfu(device)"]
        for r in rows[1:]:
            assert r.extra["policy"] == r.policy.split("(")[0]
        assert "policy" not in rows[0].extra      # default stays absent
        # per-policy sweep rows == the per-policy single runs, exactly
        for r in rows:
            pol = r.extra.get("policy", "wtinylfu")
            single = simulate_trace(self.TR, 64, assoc=8, policy=pol,
                                    window_frac=0.1)
            assert r.hits == single.hits, pol

    def test_multi_policy_grid_rejects_vmap(self):
        with pytest.raises(ValueError):
            simulate_sweep(self.TR, [64], policies=("wtinylfu", "lfu"),
                           assoc=8, mode="vmap")

    def test_sequential_rows_carry_shards_merge_integrity(self):
        """The row-schema bug this satellite fixes: sequential-mode sweep
        rows silently omitted the shards/merge_every/integrity (and
        streams) knobs that simulate_trace rows carry — a sweep row must
        round-trip every config knob that shaped it."""
        rows = simulate_sweep(self.TR, [64], shards=2, merge_every=512,
                              integrity=True, mode="sequential")
        single = simulate_trace(self.TR, 64, shards=2, merge_every=512,
                                integrity=True)
        for r in rows:
            assert r.extra["shards"] == 2
            assert r.extra["merge_every"] == 512
            assert r.extra["integrity"] is True
        knobs = ("policy", "shards", "merge_every", "integrity", "streams")
        assert {k: rows[0].extra.get(k) for k in knobs} == \
            {k: single.extra.get(k) for k in knobs}

    def test_row_extra_covers_every_knob(self):
        assert _row_extra(DeviceWTinyLFU(64), None, False) == {}
        e = _row_extra(DeviceWTinyLFU(64, shards=2, integrity=True,
                                      streams=3, merge_every=128),
                       None, False)
        assert e == {"shards": 2, "merge_every": 128, "integrity": True,
                     "streams": 3}
        e = _row_extra(DeviceWTinyLFU(64, assoc=8, policy="arc"), None,
                       False)
        assert e == {"policy": "arc"}


# ===========================================================================
# satellite: policy-parameterized property tests (hypothesis shim)
# ===========================================================================

def _prop_cfg(policy: str) -> DeviceWTinyLFU:
    return DeviceWTinyLFU(24, assoc=4, policy=policy,
                          window_frac=_wf(policy), sample_factor=8)


def _resident_counts(spec, cfg, state):
    """(window, main) resident record counts from the table meta columns."""
    wtab = np.asarray(state["wtab"]).reshape(-1, spec.wcols)
    mtab = np.asarray(state["mtab"]).reshape(-1, spec.mcols)
    res = []
    for tab, col in ((wtab, WT_META), (mtab, MT_META)):
        meta = tab[:, col]
        res.append(int(((meta != _I32_MAX) & (meta != _EMPTY)).sum()))
    return tuple(res)


@settings(max_examples=4, deadline=None)
@given(pol=st.sampled_from(POLICIES), seed=st.integers(0, 2**31 - 1))
def test_resident_count_never_exceeds_capacity(pol, seed):
    cfg = _prop_cfg(pol)
    rng = np.random.default_rng(seed)
    tr = rng.integers(0, 300, size=600).astype(np.int64)
    _, state, _ = simulate_trace(tr, cfg.capacity, return_state=True,
                                 assoc=cfg.assoc, policy=pol,
                                 window_frac=_wf(pol))
    w, m = _resident_counts(cfg.spec(), cfg, state)
    assert w <= cfg.window_cap
    assert m <= cfg.main_cap
    assert w + m <= cfg.capacity + (1 if pol in ("arc", "lfu") else 0)


@settings(max_examples=3, deadline=None)
@given(pol=st.sampled_from(POLICIES), seed=st.integers(0, 2**31 - 1))
def test_hit_never_changes_resident_set(pol, seed):
    """A hit must not evict: stepping one access at a time, the resident
    key set after any hit equals the set before it (refreshes/mark bits
    may change; membership may not)."""
    cfg = _prop_cfg(pol)
    spec = cfg.spec()
    params = cfg.params()
    state = init_step_state(spec, cfg.window_cap, cfg.main_cap)
    step = jax.jit(step_ref, static_argnums=0)
    rng = np.random.default_rng(seed)
    tr = rng.zipf(1.4, size=250).astype(np.int64) % 200

    def resident_keys(st_):
        out = set()
        for tab, cols in ((np.asarray(st_["wtab"]).reshape(-1, spec.wcols),
                           (0, 1, WT_META)),
                          (np.asarray(st_["mtab"]).reshape(-1, spec.mcols),
                           (MT_LO, MT_HI, MT_META))):
            lo_c, hi_c, meta_c = cols
            ok = (tab[:, meta_c] != _I32_MAX) & (tab[:, meta_c] != _EMPTY)
            for row in tab[ok]:
                out.add((np.uint32(row[lo_c]).item(),
                         np.uint32(row[hi_c]).item()))
        return out

    lo = np.asarray(tr & 0xFFFFFFFF, np.uint32)
    hi = np.asarray(tr >> 32, np.uint32)
    import jax.numpy as jnp
    for i in range(len(tr)):
        before = resident_keys(state)
        state, hit = step(spec, params, state,
                          jnp.asarray(lo[i:i + 1]), jnp.asarray(hi[i:i + 1]))
        if int(np.asarray(hit)[0]):
            assert resident_keys(state) == before, (pol, i)


@settings(max_examples=3, deadline=None)
@given(pol=st.sampled_from(POLICIES), seed=st.integers(0, 2**31 - 1))
def test_poisoned_lane_cannot_perturb_neighbor(pol, seed):
    """streams=2 lane isolation across the policy panel: lane 1 replaying
    adversarial churn (every key unique — pure pollution) must leave lane
    0's hit count identical to the streams=1 run of the same trace."""
    cfg = _prop_cfg(pol)
    rng = np.random.default_rng(seed)
    good = rng.zipf(1.3, size=500).astype(np.int64) % 300
    poison = (10**9 + np.arange(500)).astype(np.int64)
    solo = simulate_trace(good, cfg.capacity, assoc=cfg.assoc, policy=pol,
                          window_frac=_wf(pol))
    duo = simulate_trace(np.stack([good, poison]), cfg.capacity,
                         assoc=cfg.assoc, policy=pol,
                         window_frac=_wf(pol), streams=2)
    assert duo.extra["lane_hits"][0] == solo.hits, pol
