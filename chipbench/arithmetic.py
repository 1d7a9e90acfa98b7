"""The arithmetic of an answer cell whose language model names it: the
``.llm.json`` gives, beside ``reference``, the module under ``arithmetic``
that counts what its equations need (``window_work(ctx, llm)``: the
window's ``prefills`` and ``steps``, their ``prefill_flops``, ``step_flops``
and ``step_bytes``, from the program's counters). The readers
``llm_answer_mfu``, ``llm_prefill_roofline.answer`` and
``llm_step_roofline.answer`` go through here, so a further language model
brings an arithmetic file and no reader."""

from __future__ import annotations

import importlib

from chipbench.flops_decoder import llm_config
from chipbench.metriclib import kernel_launches


def window_work(ctx) -> dict | None:
    """None where the deployment has no language model, its configuration
    names no arithmetic (an older cell's), or no decoder ran."""
    llm = llm_config(ctx.config)
    if not llm or "arithmetic" not in llm:
        return None
    return importlib.import_module(llm["arithmetic"]).window_work(ctx, llm)


def held_share(ctx, label: str, calls: int) -> float:
    """Of the window's ``calls`` launches under ``label``, the share the
    trace holds: 1 but where the profiler's buffer filled before the window
    ended (PERF.md section 7, PR 34 (a)); the device seconds of a label are
    the held launches', so its work has to be theirs too."""
    return min(kernel_launches(ctx, label), calls) / calls
