"""The GETs' share of their roofline: the least time their work needs,
bytes (``bench/roofline.py``, from live requests and hits) over the
chips' peak HBM bandwidth, against the device-busy time of the GET calls.
Memory bandwidth bounds a lookup; it does no arithmetic to speak of."""
from bench import roofline


def reduce(traced):
    calls = traced.calls("bench.get")
    answers = traced.answers("get")
    if not calls or not traced.modules or traced.peak is None:
        return None
    busy = sum(traced.busy_in(s.start, s.end) for s in calls) * 1e-9
    if busy <= 0:
        return None
    work = sum(roofline.get_bytes(a["live"], a["hits"], traced.neighborhood,
                                  traced.val_words) for a in answers)
    least = work / (traced.peak["hbm_bytes_per_s"] * traced.n_chips)
    return 100.0 * least / busy
