"""``SteppingApplyNode``: a select whose UDF's launch does not finish every
row. Rows join a running decode batch at a step boundary and leave when done,
in finish order; a retract frees a slot; a full cache queues and drains; a
snapshot holds the rows, not the cache; and ``JaxChat`` answers through the
question-answering template's REST door."""

from __future__ import annotations

import json
import pickle
import threading
import time
import types
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.blocks import DeltaBatch
from pathway_tpu.engine.operators import MicrobatchApplyNode, MicrobatchUdfSpec, SteppingApplyNode
from pathway_tpu.engine.runtime import TickWakeup
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.ops.decoder import DecoderConfig
from pathway_tpu.xpacks.llm.llms import JaxChat
from reference_decoder import TINY, init_params
from utils import keyed_rows_of

CFG = DecoderConfig.from_hf(TINY, jnp.float32)
PROMPTS = {k: f"question {k} about " + " ".join(f"w{k * 7 + j}" for j in range(3 + k)) for k in range(1, 8)}


@pytest.fixture(scope="module")
def chat() -> JaxChat:
    return JaxChat(CFG, params=init_params(TINY, 1), max_tokens=6, cache_rows=2, cache_len=64)


@pytest.fixture(scope="module", params=["latent", "hybrid"])
def served_chat(request, chat) -> JaxChat:
    """The chat of the cases that go through ``pw.run()``, once a model kind:
    latent attention with experts, and state-space layers with grouped-query
    attention (two kinds of slot under one slot number)."""
    if request.param == "latent":
        return chat
    import reference_hybrid as hybrid

    cfg = DecoderConfig.from_hf(hybrid.TINY, jnp.float32)
    assert cfg.recurrent_layers == 9 and cfg.mixers.count("gqa") == 1
    return JaxChat(cfg, params=hybrid.init_params(hybrid.TINY, 1), max_tokens=6, cache_rows=2, cache_len=64)


def alone(chat: JaxChat, key: int, n: int | None = None) -> str:
    """What the model gives that row by itself, through the blocking batch call."""
    return chat.func([PROMPTS[key]], [n])[0]


class _Live:
    def is_finished(self):
        return False


def runtime(period_ms: float = 1e-6):
    """A streaming runtime as the node sees it. The period is the time a tick
    may go on stepping; next to nothing makes every frontier one step."""
    return types.SimpleNamespace(streaming=True, connectors=[_Live()], autocommit_duration_ms=period_ms,
                                 wakeup=TickWakeup(), in_flight={})


def node_of(chat: JaxChat, rt) -> SteppingApplyNode:
    spec = MicrobatchUdfSpec(
        "a", lambda b: ([b.data["q"]], [b.data["n"]]), chat.func, ["max_tokens"], False,
    )
    node = SteppingApplyNode(["q", "a"], ["q"], lambda b: {"q": b.data["q"]}, [spec],
                             stepper=chat.microbatch_stepper(), runtime=rt)
    node.node_index = 7
    return node


def rows(keys, ns, time, diff=1):
    return DeltaBatch.from_rows(keys, [(PROMPTS[k], n) for k, n in zip(keys, ns)], ["q", "n"], time,
                                diffs=[diff] * len(keys))


def run_until_empty(node, t0: int, limit: int = 200):
    """Frontiers from tick ``t0`` on; returns [(tick, key, answer)] in the order emitted."""
    out = []
    for t in range(t0, t0 + limit):
        for b in node.on_frontier(t):
            out += [(t, int(k), a) for k, a, d in zip(b.keys, b.data["a"], b.diffs) if d > 0]
        if not node.waiting and not node.inflight:
            return out
    raise AssertionError("the node did not drain")


def test_a_row_joins_a_running_batch_and_rows_leave_in_finish_order(chat):
    rt = runtime()
    node = node_of(chat, rt)
    assert node.process([rows([1], [12], 0)], 0) == []
    assert node.on_frontier(0) == [] and node.on_frontier(1) == []
    assert node.stepper.live() == 1 and rt.in_flight[7] == 1
    # two more arrive while the first decodes: one joins at the next step, one waits for a slot
    node.process([rows([2, 3], [3, 4], 2)], 2)
    assert node.on_frontier(2) == []
    assert node.stepper.live() == 2 and list(node.waiting) == [3] and rt.in_flight[7] == 3
    out = run_until_empty(node, 3)
    assert [k for _t, k, _a in out] == [2, 3, 1]  # finish order, not submit order
    assert len({t for t, _k, _a in out}) == 3  # each in the tick in which it finished
    assert {k: a for _t, k, a in out} == {1: alone(chat, 1, 12), 2: alone(chat, 2, 3), 3: alone(chat, 3, 4)}
    assert [len(a.split()) for _t, _k, a in out] == [3, 4, 12]
    assert node.on_frontier(999) == [] and rt.in_flight[7] == 0 and node.stepper.free() == 2


def test_a_tick_yields_when_a_row_finishes_an_arrival_is_due_or_the_period_has_passed(chat):
    rt = runtime(period_ms=60_000)
    node = node_of(chat, rt)
    node.process([rows([1, 2], [5, 40], 0)], 0)
    [b] = node.on_frontier(0)  # no arrival, a long period: steps until the first row is done
    assert b.keys.tolist() == [1] and node.stepper.live() == 1
    before = len(node.stepper.session.rows[2].out)
    rt.wakeup.request()  # an arrival asks the loop for a tick: one more step, then the tick ends
    assert node.on_frontier(1) == [] and len(node.stepper.session.rows[2].out) == before + 1
    assert node.on_frontier(1) == []  # the frontier round comes back within the tick: nothing more
    assert len(node.stepper.session.rows[2].out) == before + 1


def test_a_row_retracted_in_flight_frees_its_slot_and_emits_nothing(chat):
    rt = runtime()
    node = node_of(chat, rt)
    node.process([rows([1, 2, 3], [9, 9, 2], 0)], 0)
    node.on_frontier(0)
    assert node.stepper.free() == 0 and list(node.waiting) == [3]
    assert node.process([rows([1], [9], 1, diff=-1)], 1) == []  # in flight: cancelled in the stepper
    assert node.process([rows([3], [2], 1, diff=-1)], 1) == []  # waiting: cancelled in the buffer
    assert node.stepper.free() == 1 and not node.waiting and list(node.inflight) == [2]
    out = run_until_empty(node, 1)
    assert [(k, a) for _t, k, a in out] == [(2, alone(chat, 2, 9))]
    # settled: the retract replays what was emitted
    [b] = node.process([rows([2], [9], 50, diff=-1)], 50)
    assert b.diffs.tolist() == [-1] and b.data["a"].tolist() == [alone(chat, 2, 9)]


def test_a_full_cache_queues_oldest_first_and_drains(chat):
    rt = runtime()
    node = node_of(chat, rt)
    node.process([rows([1, 2, 3, 4, 5], [2, 2, 2, 2, 2], 0)], 0)
    out = run_until_empty(node, 0)
    assert [k for _t, k, _a in out] == [1, 2, 3, 4, 5]
    assert all(a == alone(chat, k, 2) for _t, k, a in out)
    assert node.stepper.free() == 2


def test_a_drain_steps_until_nothing_is_left(chat):
    node = node_of(chat, types.SimpleNamespace(streaming=False, connectors=[], autocommit_duration_ms=20))
    node.process([rows([1, 2, 3], [4, 2, 3], 0)], 0)
    [b] = node.on_frontier(0)
    assert sorted(b.keys.tolist()) == [1, 2, 3] and not node.inflight and not node.waiting


def test_a_snapshot_holds_the_rows_in_flight_and_restored_they_are_answered_once(chat):
    rt = runtime()
    node = node_of(chat, rt)
    node.process([rows([1, 2, 3], [6, 5, 3], 0)], 0)
    for t in range(3):
        assert node.on_frontier(t) == []
    state = pickle.loads(pickle.dumps(node.snapshot_state()))
    assert sorted(state) == ["emitted", "inflight", "waiting"] and list(state["inflight"]) == [1, 2]
    fresh = node_of(chat, runtime())  # another process: an empty cache
    fresh.restore_state(state)
    assert list(fresh.waiting) == [1, 2, 3] and not fresh.inflight  # in flight first, then the queue
    out = run_until_empty(fresh, 10)
    assert sorted((k, a) for _t, k, a in out) == [(1, alone(chat, 1, 6)), (2, alone(chat, 2, 5)),
                                                  (3, alone(chat, 3, 3))]


def test_other_batched_udfs_keep_the_flush_path(chat):
    """The kind of node is chosen by what the UDF declares: an embedder-like
    UDF (no ``microbatch_stepper``) still builds a ``MicrobatchApplyNode``."""
    from pathway_tpu.internals.table import _microbatch_factory
    from pathway_tpu.internals.udfs import UDF

    class Doubler(UDF):
        is_batched = True

        def __init__(self):
            super().__init__(_fn=lambda xs: [x * 2 for x in xs], return_type=int)

    G.clear()
    t = pw.debug.table_from_markdown("q | x\nhello | 1")
    plain = _microbatch_factory({"y": Doubler()(t.x)}, t, pw.schema_from_types(y=int))()
    stepping = _microbatch_factory({"a": chat(t.q)}, t, pw.schema_from_types(a=str))()
    assert type(plain) is MicrobatchApplyNode and type(stepping) is SteppingApplyNode
    with pytest.raises(ValueError, match="only batched UDF"):
        _microbatch_factory({"a": chat(t.q), "y": Doubler()(t.x)}, t, pw.schema_from_types(a=str, y=int))


def test_a_select_over_a_stream_answers_every_row_as_the_model_does_alone(served_chat):
    """Through ``pw.run()`` on the normal path: rows arrive over several ticks
    with their own ``max_tokens``."""
    chat = served_chat
    G.clear()

    class Questions(pw.io.python.ConnectorSubject):
        def run(self):
            for k in (1, 2, 3, 4, 5):
                self.next(k=k, q=PROMPTS[k], n=2 + k)
                time.sleep(0.03)

    t = pw.io.python.read(Questions(), schema=pw.schema_from_types(k=int, q=str, n=int))
    got = keyed_rows_of(t.select(t.k, a=chat(t.q, max_tokens=t.n)))
    assert sorted(got.values()) == [(k, alone(chat, k, 2 + k)) for k in (1, 2, 3, 4, 5)]


def _post(port: int, route: str, payload: dict):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{route}", json.dumps(payload).encode(),
                                 {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def test_the_template_answers_through_v2_answer_with_its_context_documents(served_chat, monkeypatch):
    """``BaseRAGQuestionAnswerer(llm=JaxChat(...))`` behind ``QARestServer``:
    same tick loop, microbatch node, server and store as any other chat. The
    loop sleeps on its period between questions, never over a row in flight."""
    from chipbench.pipelines._store import free_port
    from pathway_tpu.internals.run import current_runtime
    from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.prompts import prompt_qa_geometric_rag
    from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer

    chat = served_chat
    G.clear()
    slept_with_rows: list[int] = []
    sound = TickWakeup.wait

    def wait(self, timeout):
        slept_with_rows.append(sum(current_runtime().in_flight.values()))
        return sound(self, timeout)

    monkeypatch.setattr(TickWakeup, "wait", wait)
    texts = [f"document {i} holds " + " ".join(f"w{i * 5 + j}" for j in range(12)) for i in range(8)]
    docs = pw.debug.table_from_rows(pw.schema_from_types(data=str), [(t,) for t in texts])
    store = DocumentStore(docs, retriever_factory=BruteForceKnnFactory(embedder=SentenceTransformerEmbedder("tiny")))
    rag = BaseRAGQuestionAnswerer(llm=chat, indexer=store, search_topk=3)
    port = free_port()
    rag.build_server("127.0.0.1", port)
    got: dict = {}

    def client():
        try:
            for _ in range(200):
                try:
                    if _post(port, "/v1/statistics", {}).get("file_count") == len(texts):
                        break
                except OSError:
                    pass
                time.sleep(0.05)
            got["plain"] = _post(port, "/v2/answer", {"prompt": "what holds w7"})
            got["docs"] = _post(port, "/v2/answer", {"prompt": "what holds w7", "return_context_docs": True})
        finally:
            current_runtime().request_stop()

    th = threading.Thread(target=client, daemon=True)
    th.start()
    pw.run(monitoring_level="none")
    th.join(timeout=30)
    body = got["docs"]
    assert got["plain"] == body["response"] and len(body["response"].split()) == 6
    assert len(body["context_docs"]) == 3 and all(d["text"] in texts for d in body["context_docs"])
    prompt = prompt_qa_geometric_rag("what holds w7", [d["text"] for d in body["context_docs"]])
    assert body["response"] == chat.func([prompt], [None])[0]
    assert all(0 <= int(t) < chat.model.cfg.vocab_size for t in body["response"].split())
    assert slept_with_rows and not any(slept_with_rows)
