"""Open-loop retrieval: the bodies the generator kept (a sample drawn from
the seed, the longest query in it) against the reference's exact top-k over
archive and live rows."""

from chipbench import check

GAP_NAMES = ("host_with_requests_in_flight", "waiting_for_arrivals")


def collect(gen, cell, seed, window) -> list:
    return [[text, body] for _i, text, body in window["sample"]]


def numbers(cell, seed, sample, window, eparams, rparams, archive, setup_texts) -> dict:
    ref = check.Reference(cell.config, eparams, setup_texts, archive)
    return check.retrieve_numbers(ref, sample, cell.traffic["payload"]["k"])


def in_flight(window, offset_ns: int) -> list:
    """When a request was in the server's hands, on the trace's clock."""
    t0 = window["start_ns"] + offset_ns
    return [
        (t0 + int((d + late / 1e3) * 1e9), t0 + int((d + lat / 1e3) * 1e9))
        for d, late, lat in zip(window["due_s"], window["late_ms"], window["latency_ms"])
        if lat is not None
    ]
