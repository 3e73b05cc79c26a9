"""Plain reference of a set-associative W-TinyLFU cache (paper §3-§4).

A straightforward per-access loop over Python dicts and lists, written
from the semantics and sharing no code with the system under test:

- Frequency sketch (paper §3): 4-bit counters in ``rows`` rows of
  ``width`` (8 packed per int32 word on the device), conservative
  ("minimal") increment saturating at ``cap``, a doorkeeper Bloom filter of
  ``dk_bits`` bits and 3 probes in front (§3.4.2: a first visit only sets
  the doorkeeper; a key whose bits were all set counts in the rows), and
  the §3.3 reset: once ``sample_size`` additions are reached every counter
  is halved, the doorkeeper cleared and the addition count halved.
  Estimate = row minimum + 1 if the doorkeeper holds the key.
- Window (§4): per-set LRU of ``window_cap`` entries over pow2 sets.
  A miss enters the key's window set; a full set pushes its least
  recent entry out as the admission candidate.
- Main: an SLRU over pow2 sets of ``ways`` slots, power-of-two-choices
  placement (two hashed sets per key), per-set protected budget
  ``max(1, usable * prot_cap // main_cap)``.  A candidate takes a free
  way of its first, then its second set; otherwise the weakest record of
  both sets (probation before protected, least recent first) is the
  victim, and the candidate replaces it only when its estimate is
  strictly greater (§3: ties keep the incumbent).
- Hashing: the 32-bit two-lane mixer the device engine uses (TPUs have
  no 64-bit multiply), so the reference places and counts each key where
  the device must.

``simulate(keys, geometry, warmup)`` returns the per-access hit flags and
the final state in the device's packed layout, for an exact comparison;
``compare`` holds one replay of the program against it, and ``answer``
puts the reference (or the control) in the program's place.
"""
from __future__ import annotations

import numpy as np

M1, M2 = 0x7FEB352D, 0x846CA68B
HI_XOR = 0x85EBCA6B
PROBE_SALTS = (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F,
               0x165667B1, 0xD3A2646C, 0xFD7046C5, 0xB55A4F09)
DK_XOR = 0xDEADBEEF
WSET_SALT, MSET_SALT, MSET2_SALT = 0x1B873593, 0xCC9E2D51, 0x38495AB5
ROWS, DK_PROBES, PROT = 4, 3, 1 << 30
STATE_LEAVES = ("counters", "doorkeeper")     # compared word for word


def _mix32(x):
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(M1)
    x ^= x >> np.uint32(15)
    x *= np.uint32(M2)
    x ^= x >> np.uint32(16)
    return x


def _hash(lo, hi, salt: int, mod: int) -> np.ndarray:
    s = np.uint32(salt & 0xFFFFFFFF)
    with np.errstate(over="ignore"):
        h = _mix32(lo + s) ^ _mix32(hi ^ np.uint32(HI_XOR) ^ s)
    return (h & np.uint32(mod - 1)).astype(np.int64)


def _pow2ceil(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def geometry(capacity: int, assoc: int, sample_factor: int,
             window_frac: float, protected_frac: float = 0.8) -> dict:
    """Table and sketch sizes of one W-TinyLFU cache, from its settings."""
    window_cap = max(1, int(round(capacity * window_frac)))
    main_cap = max(1, capacity - window_cap)
    if main_cap <= assoc:
        main_sets, ways = 1, main_cap
    else:
        main_sets = 1 << ((main_cap // assoc).bit_length() - 1)
        ways = -(-main_cap // main_sets)
    main_sets = _pow2ceil(-(-main_cap // ways))
    window_sets = _pow2ceil(-(-window_cap // ways))
    sample = sample_factor * capacity

    def usable(cap, n):
        base, rem = divmod(cap, n)
        return [base + (1 if s < rem else 0) for s in range(n)]

    return {
        "window_cap": window_cap, "main_cap": main_cap, "ways": ways,
        "prot_cap": max(1, int(main_cap * protected_frac)),
        "window_usable": usable(window_cap, window_sets),
        "main_usable": usable(main_cap, main_sets),
        "sample": sample, "cap": min(15, max(1, sample_factor - 1)),
        "width": max(8, _pow2ceil(max(1, sample // ROWS))),
        "dk_bits": max(32, _pow2ceil(sample * 4)),
    }


def simulate(keys: np.ndarray, g: dict, warmup: int = 0, *,
             aging: bool = True) -> dict:
    """Replay ``keys`` (uint64/int64, one stream) through a cold cache.

    ``aging=False`` skips the §3.3 reset: the benchmark's control, a cache
    that breaks the aging guarantee, must fail the comparison.
    """
    keys = np.asarray(keys).astype(np.uint64)
    lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    width, dk_bits = g["width"], g["dk_bits"]
    probes = np.stack([r * width + _hash(lo, hi, PROBE_SALTS[r], width)
                       for r in range(ROWS)], axis=1).tolist()
    dkps = np.stack([_hash(lo, hi, PROBE_SALTS[p] ^ DK_XOR, dk_bits)
                     for p in range(DK_PROBES)], axis=1).tolist()
    nws, nms = len(g["window_usable"]), len(g["main_usable"])
    wsets = _hash(lo, hi, WSET_SALT, nws).tolist()
    m1s = _hash(lo, hi, MSET_SALT, nms).tolist()
    m2s = _hash(lo, hi, MSET2_SALT, nms).tolist()
    keyl = keys.tolist()

    counters = [0] * (ROWS * width)
    dk = bytearray(dk_bits)
    size = 0
    sample, cap = g["sample"], g["cap"]
    wus, mus = g["window_usable"], g["main_usable"]
    prot_cap, main_cap = g["prot_cap"], g["main_cap"]
    window = [dict() for _ in range(nws)]   # key -> access index (LRU)
    main = [dict() for _ in range(nms)]     # key -> meta: PROT bit | stamp
    home = {}                               # key -> its main set
    pos = {}                                # key -> an access of that key
    hits = np.zeros(len(keyl), np.int32)
    resets = admitted = rejected = 0

    def estimate(k):
        i = pos[k]
        p0, p1, p2, p3 = probes[i]
        b0, b1, b2 = dkps[i]
        est = min(counters[p0], counters[p1], counters[p2], counters[p3])
        return est + (1 if dk[b0] and dk[b1] and dk[b2] else 0)

    for t, k in enumerate(keyl):
        # -- sketch add: a key whose doorkeeper bits were all set before
        # this access counts in the rows (conservative increment)
        b0, b1, b2 = dkps[t]
        present = dk[b0] and dk[b1] and dk[b2]
        dk[b0] = dk[b1] = dk[b2] = 1
        if present:
            pr = probes[t]
            vals = [counters[i] for i in pr]
            m = min(vals)
            if m < cap:
                for i, v in zip(pr, vals):
                    if v == m:
                        counters[i] = m + 1
        size += 1
        if aging and size >= sample:        # §3.3 reset
            counters = [v >> 1 for v in counters]
            dk = bytearray(dk_bits)
            size //= 2
            resets += 1
        # -- lookups
        ws = window[wsets[t]]
        if k in ws:
            del ws[k]
            ws[k] = t
            hits[t] = 1
            continue
        s = home.get(k)
        if s is not None:
            st = main[s]
            st[k] = PROT | t
            prot = [x for x, m in st.items() if m >= PROT]
            if len(prot) > max(1, mus[s] * prot_cap // max(1, main_cap)):
                st[min(prot, key=st.__getitem__)] = t
            hits[t] = 1
            continue
        # -- miss: the key enters its window set; overflow pushes a candidate
        pos[k] = t
        ws[k] = t
        if len(ws) <= wus[wsets[t]]:
            continue
        cand = next(iter(ws))
        del ws[cand]
        ic = pos[cand]
        c1, c2 = m1s[ic], m2s[ic]
        if len(main[c1]) < mus[c1]:
            target = c1
        elif len(main[c2]) < mus[c2]:
            target = c2
        else:
            best = None
            for c in (c1, c2):
                for x, m in main[c].items():
                    if best is None or m < best[0]:
                        best = (m, c, x)
            if best is None:                # both choice sets hold no way
                continue
            _, target, victim = best
            if estimate(cand) > estimate(victim):
                del main[target][victim]
                del home[victim]
                admitted += 1
            else:
                rejected += 1
                continue
        main[target][cand] = t
        home[cand] = target

    words = np.asarray(counters, np.int64).reshape(-1, 8)
    packed = np.zeros(words.shape[0], np.int64)
    for j in range(8):
        packed |= words[:, j] << (4 * j)
    bits = np.frombuffer(bytes(dk), np.uint8).astype(np.int64).reshape(-1, 32)
    dkw = np.zeros(bits.shape[0], np.int64)
    for j in range(32):
        dkw |= bits[:, j] << j
    counted = int(hits[warmup:].sum())
    return {
        "hits": hits,
        "counters": packed.astype(np.uint32).view(np.int32),
        "doorkeeper": dkw.astype(np.uint32).view(np.int32),
        "size": size, "t": len(keyl), "counted_hits": counted,
        "resets": resets, "admitted": admitted, "rejected": rejected,
        "main_full": sum(len(x) for x in main) == main_cap,
        "evicting": admitted + rejected > 0,
    }


def _geometry_of(kwargs: dict) -> dict:
    return geometry(kwargs["capacity"], kwargs["assoc"],
                    kwargs.get("sample_factor", 8),
                    kwargs.get("window_frac", 0.01))


def _replay(kwargs: dict, mix: dict, keys: np.ndarray, aging: bool) -> dict:
    return simulate(keys, _geometry_of(kwargs), int(mix.get("warmup", 0)),
                    aging=aging)


def answer(kwargs: dict, mix: dict, keys: np.ndarray, *,
           control: bool = False) -> dict:
    """The reference's own answer to one replay, in the form the entry's
    ``readback`` gives.  ``control=True`` leaves the §3.3 aging out: the
    benchmark's control, which the comparison must fail."""
    ref = _replay(kwargs, mix, keys, aging=not control)
    return {"hits": ref["hits"], "counted": ref["counted_hits"],
            **{leaf: ref[leaf] for leaf in STATE_LEAVES}}


def compare(kwargs: dict, mix: dict, keys: np.ndarray,
            got: dict) -> tuple[dict, dict]:
    """Compare one replay's ``got`` with the reference.

    Returns the compared numbers (each has a limit in the configuration)
    and the reference's own counts of how full the tables were.
    """
    ref = _replay(kwargs, mix, keys, aging=True)
    words = 0
    for leaf in STATE_LEAVES:
        mine, want = np.asarray(got[leaf]).reshape(-1), ref[leaf]
        words += (int((mine != want).sum()) if mine.shape == want.shape
                  else max(mine.size, want.size))
    num = {"hits_differing": int((np.asarray(got["hits"]).reshape(-1)
                                  != ref["hits"]).sum()),
           "hit_count_gap": abs(int(got["counted"]) - ref["counted_hits"]),
           "sketch_words_differing": words}
    fill = {"main_full": int(ref["main_full"]),
            "evicting": int(ref["evicting"]),
            **{k: ref[k] for k in ("resets", "admitted", "rejected")}}
    return num, fill
