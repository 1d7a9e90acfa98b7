"""Chat-model wrappers (reference ``xpacks/llm/llms.py:97-549``).

Remote chats (OpenAI/LiteLLM/Cohere) are async UDFs gated on their client
libraries — with INJECTABLE transports (the connector fake-client pattern,
r5): pass ``client=`` (OpenAI/Cohere-shaped object) or ``acompletion=``
(LiteLLM-shaped coroutine) and the wrapper's request/parse/retry/capacity
plumbing runs without the real library (``tests/test_llm_wrappers.py``).
``HFPipelineChat`` runs a local transformers pipeline (torch CPU in this
image). ``JaxChat`` runs a decoder on the chip, inside the dataflow
(``ops/decoder.py``: latent attention, a sparse expert layer, a latent
cache): questions join a running decode batch at a step boundary and leave
when answered. All accept the reference's message-dict format and return
strings, and ``BaseRAGQuestionAnswerer`` takes any of them as its ``llm``::

    chat = JaxChat(DecoderConfig.from_hf(config_json), params=params, max_tokens=48)
    rag = BaseRAGQuestionAnswerer(llm=chat, indexer=DocumentStore(docs, retriever_factory), search_topk=6)
    rag.build_server("127.0.0.1", 8000)  # POST /v2/answer {"prompt": ...}
"""

from __future__ import annotations

from typing import Any

from pathway_tpu.internals.udfs import UDF, async_executor
from pathway_tpu.xpacks.llm._utils import require


class BaseChat(UDF):
    """Chat UDF: list-of-message-dicts (or str) → str."""


def _as_messages(value: Any) -> list[dict]:
    if isinstance(value, str):
        return [{"role": "user", "content": value}]
    if hasattr(value, "value"):  # pw.Json
        value = value.value
    return list(value)


class OpenAIChat(BaseChat):
    def __init__(
        self,
        model: str = "gpt-4o-mini",
        capacity: int | None = None,
        retry_strategy: Any = None,
        cache_strategy: Any = None,
        client: Any = None,
        **openai_kwargs,
    ):
        # no `timeout` wrapper param: a timeout= kwarg flows through to the
        # provider API (request-side bound), matching the pre-r5 behavior
        if client is None:
            require("openai", "OpenAIChat")
            import openai

            client = openai.AsyncOpenAI(
                **{k: v for k, v in openai_kwargs.items() if k in ("api_key", "base_url")}
            )
        extra = {k: v for k, v in openai_kwargs.items() if k not in ("api_key", "base_url")}
        self.model = model

        async def chat(messages) -> str:
            r = await client.chat.completions.create(
                model=model, messages=_as_messages(messages), **extra
            )
            return r.choices[0].message.content or ""

        super().__init__(
            _fn=chat,
            return_type=str,
            executor=async_executor(capacity=capacity, retry_strategy=retry_strategy),
            cache_strategy=cache_strategy,
        )


class LiteLLMChat(BaseChat):
    def __init__(
        self,
        model: str,
        capacity: int | None = None,
        retry_strategy: Any = None,
        cache_strategy: Any = None,
        acompletion: Any = None,
        **kwargs,
    ):
        if acompletion is None:
            require("litellm", "LiteLLMChat")
            import litellm

            acompletion = litellm.acompletion
        self.model = model

        async def chat(messages) -> str:
            r = await acompletion(model=model, messages=_as_messages(messages), **kwargs)
            return r.choices[0].message.content or ""

        super().__init__(
            _fn=chat,
            return_type=str,
            executor=async_executor(capacity=capacity, retry_strategy=retry_strategy),
            cache_strategy=cache_strategy,
        )


class CohereChat(BaseChat):
    def __init__(
        self,
        model: str = "command",
        capacity: int | None = None,
        retry_strategy: Any = None,
        cache_strategy: Any = None,
        client: Any = None,
        **kwargs,
    ):
        if client is None:
            require("cohere", "CohereChat")
            import cohere

            client = cohere.AsyncClient()
        self.model = model

        async def chat(messages) -> str:
            msgs = _as_messages(messages)
            r = await client.chat(model=model, message=msgs[-1]["content"], **kwargs)
            return r.text

        super().__init__(
            _fn=chat,
            return_type=str,
            executor=async_executor(capacity=capacity, retry_strategy=retry_strategy),
            cache_strategy=cache_strategy,
        )


class HFPipelineChat(BaseChat):
    """Local transformers text-generation pipeline (reference ``llms.py:447``).
    Runs on CPU torch in this image; prefer remote or mock chats in the hot path."""

    def __init__(self, model: str, device: str = "cpu", call_kwargs: dict | None = None, **pipeline_kwargs):
        require("transformers", "HFPipelineChat")
        import transformers

        self.pipeline = transformers.pipeline(
            "text-generation", model=model, device=device, **pipeline_kwargs
        )
        pipe = self.pipeline
        ckw = call_kwargs or {}

        def chat(messages) -> str:
            msgs = _as_messages(messages)
            out = pipe(msgs[-1]["content"], **ckw)
            return out[0]["generated_text"]

        super().__init__(_fn=chat, return_type=str)


class _ChatStepper:
    """``JaxChat``'s rows in flight (``ops/microbatch.py`` ``RowStepper``):
    a ``DecodeSession`` with the chat's tokenizer before it and its rendering
    of the tokens after it."""

    def __init__(self, chat: "JaxChat"):
        from pathway_tpu.ops.decoder import DecodeSession

        self.chat = chat
        self.session = DecodeSession(chat.model)
        self.free, self.live, self.cancel = self.session.free, self.session.live, self.session.cancel

    def admit(self, rows: list) -> list:
        chat = self.chat
        return self._rendered(self.session.admit([
            (handle, chat.prompt_ids(args[0], kwargs.get("max_tokens")), chat.budget(kwargs.get("max_tokens")))
            for handle, args, kwargs in rows
        ]))

    def step(self) -> list:
        return self._rendered(self.session.step())

    def _rendered(self, finished: list) -> list:
        return [(handle, self.chat.render(tokens)) for handle, tokens in finished]

    @staticmethod
    def size(result: str) -> int:
        return result.count(" ") + 1


class JaxChat(BaseChat):
    """A decoder on the chip as the chat (``ops/decoder.py``), greedy.

    ``model`` is a ``DecoderConfig`` (``DecoderConfig.from_hf(config_json)``)
    and ``params`` its weights, in ``ops/decoder.py``'s layout (this image
    has no checkpoint: the tests and the benchmark make seeded ones beside
    their references). ``max_tokens`` is the length of an answer (a
    call may pass its own, ``chat(prompt, max_tokens=column)``; there is no
    end-of-sequence id, an answer is exactly that long); ``cache_rows``
    answers decode side by side, each over at most ``cache_len`` positions; a
    prompt longer than ``cache_len - max_tokens`` keeps its end.

    The prompt is the messages' contents joined by newlines under the repo's
    ``HashTokenizer`` at the model's vocabulary, after a begin-of-sequence
    id. The tokenizer hashes, so no id has a word to go back to: the answer
    is its token ids in decimal, separated by spaces.

    As a top-level column of a select it runs as a ``SteppingApplyNode``: the
    chat declares ``microbatch_stepper``, so rows join and leave a running
    decode batch across ticks. Called any other way, its batch function runs
    each batch of prompts to its end (``ops/decoder.py`` ``generate``; no
    cell of the benchmark measures that path)."""

    is_batched = True

    def __init__(self, model: Any, *, params: Any, max_tokens: int = 48, cache_rows: int = 16,
                 cache_len: int = 4096, **kwargs):
        from pathway_tpu.ops.decoder import JaxDecoder
        from pathway_tpu.ops.encoder import HashTokenizer

        self.model = JaxDecoder(model, params, cache_rows=cache_rows, cache_len=cache_len)
        self.max_tokens = max_tokens
        self.tokenizer = HashTokenizer(vocab_size=model.vocab_size, max_len=cache_len)

        def chat(messages: list, max_tokens: list | None = None) -> list[str]:
            from pathway_tpu.ops.decoder import generate

            asked = max_tokens or [None] * len(messages)
            prompts = [self.prompt_ids(m, n) for m, n in zip(messages, asked)]
            return [self.render(t) for t in generate(self.model, prompts, [self.budget(n) for n in asked])]

        super().__init__(_fn=chat, return_type=str, **kwargs)

    def budget(self, max_tokens: Any) -> int:
        return max(1, min(int(self.max_tokens if max_tokens is None else max_tokens), self.model.cache_len - 1))

    def prompt_ids(self, messages: Any, max_tokens: int | None = None):
        """[BOS] + the hashed pieces of the messages' text, the end of it kept
        where it is longer than the cache leaves room for."""
        import numpy as np

        from pathway_tpu.ops.decoder import BOS

        text = "\n".join(str(m["content"]) for m in _as_messages(messages))
        ids, lens = self.tokenizer._tok_batch([text])
        room = self.model.cache_len - self.budget(max_tokens)
        return np.concatenate([[BOS], ids[0, : lens[0]]]).astype(np.int32)[-room:]

    @staticmethod
    def render(tokens: list[int]) -> str:
        return " ".join(map(str, tokens))

    def microbatch_stepper(self) -> _ChatStepper:
        return _ChatStepper(self)

    def warm(self) -> None:
        """Compile every executable serving can ask for (``JaxDecoder.warm``)."""
        self.model.warm()


def prompt_chat_single_qa(question: str) -> Any:
    """Reference helper: wrap a question as a one-message chat (``llms.py``)."""
    import pathway_tpu as pw

    return pw.Json([dict(role="user", content=question)])
