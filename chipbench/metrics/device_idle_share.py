"""Device: share of the traced replay in which no op ran on the TPU.

1 - (time covered by ops) / (the replay's span), in percent, averaged over
the traced chips.  Outside the step program, the device's programs and
ops count as busy; inside it, the gaps between its ops count as idle at
the share that the middle session's sample of the step program shows
(the sessions do not hold the rest of the step's ops).  Read only where
that sample exists.
"""


def reduce(trace, record):
    s0, s1 = trace["span"]
    shares = []
    for c in trace["chips"].values():
        m = c["mid"]
        if not c["step"] or not m or m["span"][1] <= m["span"][0]:
            continue
        busy = sum(max(0, min(s1, b) - max(s0, a)) for a, b in c["busy"])
        gaps = (1 - m["busy_ns"] / (m["span"][1] - m["span"][0])) * (
            c["step"][1] - c["step"][0])
        shares.append(100.0 * (1.0 - (busy - gaps) / (s1 - s0)))
    return sum(shares) / len(shares) if shares else None
