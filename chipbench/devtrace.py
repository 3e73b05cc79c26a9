"""Profiler sessions around one replay, and their reduction to intervals.

The TPU profiler records every op of the step scan (~140 events per
access), so a replay of hundreds of thousands of accesses cannot be traced
whole, and closing a session takes seconds per 100k events.  A traced run
samples the window's first replay with three short sessions:

- ``head``: from the replay's call until ``HEAD_S`` later: the key
  staging, the small set-up programs and the step program's first ops;
- ``mid``: ``MID_S`` in the middle of the replay: the step program alone,
  for the gaps between its ops;
- ``tail``: from ``TAIL_S`` before the replay's predicted end until the
  call returns: the step program's last ops and the readback.

The step program runs across the gaps between the sessions.  Its first op
in the head and its last op in the tail place it (:func:`condense`); a
traced run in which they do not is an error, not a silent gap.
"""
from __future__ import annotations

import bisect
import os
import threading

HEAD_S, MID_S, TAIL_S = 0.25, 0.1, 0.3
SESSIONS = ("head", "mid", "tail")


class Sessions:
    """The three sessions of one replay, opened and closed by timer threads
    while the main thread waits on the device.  ``expected_s`` is the
    replay's length, timed on a warm replay of the same shape."""

    def __init__(self, jax, root: str, expected_s: float):
        self.jax, self.root = jax, root
        self.opts = jax.profiler.ProfileOptions()
        self.opts.python_tracer_level = 0
        self.lock, self.on, self.dirs = threading.Lock(), False, {}
        self.start("head")
        mid = max(HEAD_S, expected_s / 2)
        self.timers = [threading.Timer(HEAD_S, self.stop),
                       threading.Timer(mid, self.start, ("mid",)),
                       threading.Timer(mid + MID_S, self.stop),
                       threading.Timer(max(mid + MID_S, expected_s - TAIL_S),
                                       self.start, ("tail",))]
        for t in self.timers:
            t.start()

    def start(self, tag: str):
        with self.lock:
            if not self.on:
                d = os.path.join(self.root, tag)
                self.jax.profiler.start_trace(d, profiler_options=self.opts)
                self.dirs[tag] = d
                self.on = True

    def stop(self):
        with self.lock:
            if self.on:
                self.jax.profiler.stop_trace()
                self.on = False

    def close(self) -> dict:
        """At the replay's return: ``{tag: .xplane.pb path}``."""
        for t in self.timers:
            t.cancel()
            t.join()
        self.stop()
        return {tag: os.path.join(d, f) for tag, top in self.dirs.items()
                for d, _, fs in os.walk(top) for f in fs
                if f.endswith(".xplane.pb")}


def read_sessions(paths: dict) -> dict:
    """``{tag: [(plane, [(line, [(event, start_ns, duration_ns)])])]}`` of
    the TPU planes' "XLA Modules" and "XLA Ops" lines, each start made
    absolute (ns since the epoch) with the session's
    ``profile_start_time``."""
    import jax
    out = {}
    for tag, path in paths.items():
        pd = jax.profiler.ProfileData.from_file(path)
        t0 = next((dict(p.stats)["profile_start_time"] for p in pd.planes
                   if p.name == "Task Environment"), 0)
        out[tag] = [(p.name, [(ln.name, [(e.name, t0 + e.start_ns,
                                          e.duration_ns)
                                         for e in ln.events])
                              for ln in p.lines
                              if ln.name in ("XLA Modules", "XLA Ops")])
                    for p in pd.planes if p.name.startswith("/device:TPU:")]
    return out


def union(intervals) -> list:
    """Union of ``(start, end)`` intervals, as sorted ``[s, e]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(intervals, s, e) -> float:
    """Length of the part of ``[s, e]`` that ``intervals`` cover."""
    return sum(max(0, min(e, b) - max(s, a)) for a, b in intervals)


def _inside(iv: list, starts: list, s, e) -> bool:
    i = bisect.bisect_right(starts, s) - 1
    return i >= 0 and iv[i][0] <= s and e <= iv[i][1]


def condense(sessions: dict, span: list, step: str) -> dict:
    """The intervals the metric readers use, in ns since the epoch.

    ``sessions``: as :func:`read_sessions` gives them; ``span``: the
    harness's own ``[start_ns, end_ns]`` of the traced replay; ``step``:
    the name prefix of the step program.  Per TPU plane:

    - ``modules``: ``[name, start, end]`` of the other programs, held whole
      by a session ("XLA Modules");
    - ``step``: ``[start, end]`` of the step program: from its first op
      (or its own module event) in the head session to its last in the
      tail, an op counting for it where no other program covers it; None
      where the head or the tail holds none;
    - ``busy``: the union of the programs, the step and every op: the
      device's busy time, counted by program;
    - ``mid``: the middle session's sample of the step program:
      ``[start, end]`` from its first op to its last, ``busy_ns`` the union
      of its ops there, and ``ops`` the time of each op by name.
    """
    chips = {}
    for tag, planes in sessions.items():
        for pname, lines in planes:
            chip = chips.setdefault(pname, {"modules": {}, "ops": {},
                                            "steps": {}})
            for ln, evs in lines:
                if ln == "XLA Modules":
                    for n, s, d in evs:
                        k = (n, s)
                        chip["modules"][k] = max(d, chip["modules"].get(k, 0))
                        if n.startswith(step):
                            chip["steps"].setdefault(tag, []).append(
                                (s, s + d))
                else:
                    chip["ops"].setdefault(tag, []).extend(
                        (n, s, s + d) for n, s, d in evs)
    out = {}
    for pname, chip in chips.items():
        mods = [(n, s, s + d) for (n, s), d in chip["modules"].items()]
        other = [m for m in mods if not m[0].startswith(step)]
        iv = union((s, e) for _, s, e in other)
        starts = [a for a, _ in iv]
        ours = {tag: chip["steps"].get(tag, []) + [
                    (s, e) for _, s, e in chip["ops"].get(tag, [])
                    if not _inside(iv, starts, s, e)] for tag in SESSIONS}
        head, tail = ours.get("head"), ours.get("tail")
        placed = None
        if head and tail:
            placed = [min(s for s, _ in head), max(e for _, e in tail)]
        mid = None
        ops = [o for o in chip["ops"].get("mid", [])
               if placed and placed[0] <= o[1] and o[2] <= placed[1]]
        if ops:
            by_name = {}
            for n, s, e in ops:
                n = n.split(" = ")[0]
                by_name[n] = by_name.get(n, 0) + (e - s)
            u = union((s, e) for _, s, e in ops)
            mid = {"span": [u[0][0], u[-1][1]],
                   "busy_ns": sum(e - s for s, e in u), "ops": by_name}
        every_op = [(s, e) for v in chip["ops"].values() for _, s, e in v]
        out[pname] = {
            "modules": [list(m) for m in sorted(other, key=lambda m: m[1])],
            "step": placed, "mid": mid,
            "busy": union([(s, e) for _, s, e in other] + every_op
                          + ([tuple(placed)] if placed else []))}
    return {"span": list(span), "chips": out}


def idle_inside_step_ns(chip: dict) -> float:
    """The step program's gaps between ops, from the middle sample's share
    of them, over the whole placed step; 0 without a sample."""
    m = chip["mid"]
    if not chip["step"] or not m or m["span"][1] <= m["span"][0]:
        return 0.0
    gap = 1.0 - m["busy_ns"] / (m["span"][1] - m["span"][0])
    return gap * (chip["step"][1] - chip["step"][0])


def summary(trace: dict) -> tuple[dict, dict]:
    """``busy_s``/``window_s`` of the traced replay (averaged over the
    traced chips; the step program's gaps between ops count as idle) and
    the breakdown: the device programs and ops that took most time, and
    the longest idle gaps by where the entry stood."""
    s0, s1 = trace["span"]
    busy, progs, gaps = [], {}, []
    for chip in trace["chips"].values():
        a, b = chip["step"]
        inner = idle_inside_step_ns(chip)
        busy.append(overlap(chip["busy"], s0, s1) - inner)
        progs["step program (placed by its first and last ops)"] = (
            progs.get("step program (placed by its first and last ops)", 0)
            + (b - a) / 1e9)
        for name, s, e in chip["modules"]:
            if e > s0 and s < s1:
                name = name.split("(")[0]
                progs[name] = progs.get(name, 0) + (min(e, s1)
                                                    - max(s, s0)) / 1e9
        m = chip["mid"]
        if m:
            width = m["span"][1] - m["span"][0]
            for n, t in sorted(m["ops"].items(), key=lambda x: -x[1])[:5]:
                key = f"{n} (in the step, scaled from the middle sample)"
                progs[key] = progs.get(key, 0) + t / width * (b - a) / 1e9
        iv = [[max(x, s0), min(y, s1)] for x, y in chip["busy"]
              if y > s0 and x < s1]
        edges = [s0] + [x for xy in iv for x in xy] + [s1]
        for x, y in zip(edges[::2], edges[1::2]):
            if y > x:
                gaps.append((y - x, "before the step program: staging, "
                             "set-up programs, dispatch" if y <= a else
                             "after the step program: readback"))
        gaps.append((inner, "inside the step program: gaps between its ops "
                            "(scaled from the middle sample)"))
    n = len(trace["chips"])
    return ({"busy_s": sum(busy) / n / 1e9, "window_s": (s1 - s0) / 1e9},
            {"device_ops": sorted(([k, v / n] for k, v in progs.items()),
                                  key=lambda x: -x[1])[:10],
             "idle_gaps": [[w, g / n / 1e9] for g, w in
                           sorted(gaps, key=lambda x: -x[0])[:10]]})
