"""Pure-JAX transformer sentence encoder — the framework's flagship model.

Replaces the reference's per-row torch ``SentenceTransformerEmbedder``
(``xpacks/llm/parsers.py`` sibling, ``xpacks/llm/embedders.py:340-398``: one
``model.encode(input)`` call per row) with a batched, jitted transformer forward
pass designed for the MXU: bf16 matmuls with f32 accumulation, mean pooling over a
validity mask, L2-normalized output embeddings.

The parameter pytree carries explicit ``PartitionSpec`` sharding rules so the same
model runs single-chip or tensor+data-parallel over a ``Mesh(("data","model"))``:
attention/MLP weights shard on the model axis (column→row parallel pairs, the
Megatron layout, realized by XLA from sharding constraints rather than hand-written
collectives), activations shard on batch.

Also provides ``contrastive_train_step`` — an InfoNCE fine-tuning step (the standard
way sentence encoders are trained) used by ``__graft_entry__.dryrun_multichip`` to
prove the full dp+tp training path compiles and runs sharded.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.native import try_load as _try_load_native
from pathway_tpu import observability as _obs
from pathway_tpu.observability import device as _dev_prof

# C tokenizer kernel (None -> pure-Python fallback, bit-identical)
_pwtok_native = _try_load_native("pwtok")


class EncoderConfig(NamedTuple):
    vocab_size: int = 32768
    d_model: int = 384
    n_heads: int = 6
    n_layers: int = 6
    d_ff: int = 1536
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    #: "preln" = this framework's native pre-LN block; "bert" = the exact
    #: post-LN BERT/MiniLM block (biases + embedding LayerNorm + token types),
    #: used when loading real HuggingFace checkpoints via ``from_pretrained``
    arch: str = "preln"
    ln_eps: float = 1e-6
    #: allow the VMEM-resident pallas attention kernel (TPU, short L). MUST
    #: be False under tensor-parallel meshes: pallas_call carries no GSPMD
    #: sharding rule, so the Megatron column-split of wqkv can't partition
    #: through it (JaxSentenceEncoder(mesh=...) clears this automatically)
    pallas_attention: bool = True


def init_params(cfg: EncoderConfig, key: jax.Array) -> dict:
    """Initialize a parameter pytree: {embed, pos, layers: [..], ln_f}."""
    keys = jax.random.split(key, 2 + cfg.n_layers)
    scale = cfg.d_model ** -0.5

    def dense(k, m, n):
        return (jax.random.normal(k, (m, n), jnp.float32) * (m ** -0.5)).astype(jnp.float32)

    params: dict = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32) * scale,
        "pos": jax.random.normal(keys[1], (cfg.max_len, cfg.d_model), jnp.float32) * scale,
        "layers": [],
        "ln_f": {"g": jnp.ones((cfg.d_model,)), "b": jnp.zeros((cfg.d_model,))},
    }
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[2 + i], 6)
        params["layers"].append(
            {
                "ln1": {"g": jnp.ones((cfg.d_model,)), "b": jnp.zeros((cfg.d_model,))},
                "wqkv": dense(lk[0], cfg.d_model, 3 * cfg.d_model),
                "wo": dense(lk[1], cfg.d_model, cfg.d_model),
                "ln2": {"g": jnp.ones((cfg.d_model,)), "b": jnp.zeros((cfg.d_model,))},
                "w1": dense(lk[2], cfg.d_model, cfg.d_ff),
                "w2": dense(lk[3], cfg.d_ff, cfg.d_model),
            }
        )
    return params


def param_shardings(cfg: EncoderConfig, mesh: Mesh) -> dict:
    """PartitionSpecs mirroring init_params' tree: Megatron column/row split on the
    'model' axis; embeddings sharded on vocab; everything tiny replicated.
    Mirrors whichever architecture the config selects (preln or bert)."""
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    layer = {
        "ln1": {"g": ns(), "b": ns()},
        "wqkv": ns(None, "model"),   # column-parallel
        "wo": ns("model", None),     # row-parallel
        "ln2": {"g": ns(), "b": ns()},
        "w1": ns(None, "model"),
        "w2": ns("model", None),
    }
    if cfg.arch == "bert":
        layer = dict(
            layer,
            bqkv=ns("model"),  # column-parallel bias
            bo=ns(),
            b1=ns("model"),
            b2=ns(),
        )
    out = {
        "embed": ns("model", None),
        "pos": ns(),
        "layers": [layer for _ in range(cfg.n_layers)],
        "ln_f": {"g": ns(), "b": ns()},
    }
    if cfg.arch == "bert":
        out["tok_type"] = ns()
        out["emb_ln"] = {"g": ns(), "b": ns()}
    return out


def _layer_norm(x, g, b):
    """Single-pass LN (preln path): var = E[x²] − E[x]², so XLA folds both
    reductions into ONE pass over x — measured 2× faster than the two-pass
    jnp.var form at [512,128,384] (BASELINE.md §encoder-mfu). The BERT
    checkpoint path keeps the numerically-conservative two-pass
    ``_layer_norm_eps``."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    # clamp: catastrophic cancellation can push E[x²]−µ² slightly negative for
    # near-constant rows with large mean, and rsqrt of a negative is NaN —
    # max(·, 0) is free on the MXU (ADVICE r5)
    var = jnp.maximum(jnp.mean(x32 * x32, axis=-1, keepdims=True) - mu * mu, 0.0)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6) * g + b).astype(x.dtype)


def _use_pallas_attention() -> bool:
    # NOTE: read at TRACE time — the decision is baked into each compiled
    # executable, so PATHWAY_PALLAS_ATTENTION must be set before the first
    # encode of a given shape (flipping it later doesn't invalidate jit
    # caches; restart the process to change paths)
    import os

    if os.environ.get("PATHWAY_PALLAS_ATTENTION", "auto").lower() in ("off", "0", "false"):
        return False
    return jax.default_backend() == "tpu"


def _sdpa(q, k, v, mask, scale):
    """Fused scaled-dot-product attention on [B, L, H, hd] tensors (r5 MFU
    item): ``jax.nn.dot_product_attention`` hands XLA one fusible attention
    expression. Key-padding mask is [B, L] bool. (The pallas short-seq kernel
    enters one level up, in ``_attention``, on the FLAT layout — reshaping
    to heads first costs more than the kernel saves, measured.)"""
    return jax.nn.dot_product_attention(
        q, k, v, mask=mask[:, None, None, :], scale=scale
    )


def _attention(x, wqkv, wo, mask, n_heads, allow_pallas=True):
    """preln attention, bf16-native: MXU accumulation is f32 regardless of
    the requested OUTPUT dtype, so asking for f32 outputs only to cast them
    back (the r4 pattern) spends HBM bytes on f32 intermediates — dropping
    the f32 epilogue measured +1pt MFU on v5e (BASELINE.md §encoder-mfu).
    On TPU, short sequences run the VMEM-resident pallas kernel directly on
    the FLAT [B, L, D] layout (heads = 64-wide column slices; scores never
    touch HBM); ``allow_pallas=False`` (tensor-parallel meshes) keeps the
    GSPMD-partitionable XLA path."""
    B, L, D = x.shape
    qkv = x @ wqkv.astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    hd = D // n_heads
    if allow_pallas and _use_pallas_attention():
        from pathway_tpu.ops.attention_kernel import attention_short_flat

        ctx = attention_short_flat(q, k, v, mask, n_heads, hd ** -0.5)
        if ctx is not None:
            return ctx @ wo.astype(x.dtype)
    ctx = _sdpa(
        q.reshape(B, L, n_heads, hd),
        k.reshape(B, L, n_heads, hd),
        v.reshape(B, L, n_heads, hd),
        mask,
        hd ** -0.5,
    ).reshape(B, L, D)
    return ctx @ wo.astype(x.dtype)


def _encode_bert(params: dict, cfg: EncoderConfig, token_ids: jax.Array, mask: jax.Array) -> jax.Array:
    """Exact BERT/MiniLM forward (post-LN, biased projections, embedding LN),
    so HuggingFace checkpoints reproduce their reference embeddings
    (``xpacks/llm/embedders.py:340-398`` SentenceTransformer semantics:
    masked mean pooling + L2 norm)."""
    dt_ = cfg.dtype
    L = token_ids.shape[1]
    x = (
        params["embed"][token_ids]
        + params["pos"][:L][None, :, :]
        + params["tok_type"][0][None, None, :]
    )
    x = _layer_norm_eps(x, params["emb_ln"]["g"], params["emb_ln"]["b"], cfg.ln_eps).astype(dt_)
    for layer in params["layers"]:
        a = _attention_biased(
            x, layer["wqkv"], layer["bqkv"], layer["wo"], layer["bo"], mask, cfg.n_heads
        )
        x = _layer_norm_eps(
            (x + a).astype(jnp.float32), layer["ln1"]["g"], layer["ln1"]["b"], cfg.ln_eps
        ).astype(dt_)
        h = jnp.einsum("bld,df->blf", x, layer["w1"].astype(dt_),
                       preferred_element_type=jnp.float32) + layer["b1"]
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=False).astype(dt_)
        h = jnp.einsum("blf,fd->bld", h, layer["w2"].astype(dt_),
                       preferred_element_type=jnp.float32) + layer["b2"]
        x = _layer_norm_eps(
            x.astype(jnp.float32) + h, layer["ln2"]["g"], layer["ln2"]["b"], cfg.ln_eps
        ).astype(dt_)
    m = mask.astype(jnp.float32)[:, :, None]
    pooled = jnp.sum(x.astype(jnp.float32) * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def _layer_norm_eps(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * g + b


def _attention_biased(x, wqkv, bqkv, wo, bo, mask, n_heads):
    B, L, D = x.shape
    qkv = (
        jnp.einsum("bld,de->ble", x, wqkv.astype(x.dtype),
                   preferred_element_type=jnp.float32) + bqkv
    ).astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    hd = D // n_heads
    ctx = _sdpa(
        q.reshape(B, L, n_heads, hd),
        k.reshape(B, L, n_heads, hd),
        v.reshape(B, L, n_heads, hd),
        mask,
        hd ** -0.5,
    ).reshape(B, L, D)
    return (
        jnp.einsum("bld,de->ble", ctx, wo.astype(x.dtype),
                   preferred_element_type=jnp.float32) + bo
    ).astype(x.dtype)


def encode(params: dict, cfg: EncoderConfig, token_ids: jax.Array, mask: jax.Array) -> jax.Array:
    """Forward pass: [B, L] int32 tokens + bool mask → [B, d_model] f32 unit vectors."""
    if cfg.arch == "bert":
        return _encode_bert(params, cfg, token_ids, mask)
    x = params["embed"][token_ids].astype(cfg.dtype)
    L = token_ids.shape[1]
    x = x + params["pos"][:L][None, :, :].astype(cfg.dtype)
    for layer in params["layers"]:
        h = _layer_norm(x, layer["ln1"]["g"], layer["ln1"]["b"])
        x = x + _attention(
            h, layer["wqkv"], layer["wo"], mask, cfg.n_heads,
            allow_pallas=cfg.pallas_attention,
        )
        h = _layer_norm(x, layer["ln2"]["g"], layer["ln2"]["b"])
        # bf16-native FF (f32 epilogue casts dropped — see _attention)
        h = jax.nn.gelu(h @ layer["w1"].astype(x.dtype))
        x = x + (h @ layer["w2"].astype(x.dtype))
    x = _layer_norm(x, params["ln_f"]["g"], params["ln_f"]["b"])
    # masked mean pooling in f32, then L2-normalize (sentence-transformers pooling)
    m = mask.astype(jnp.float32)[:, :, None]
    pooled = jnp.sum(x.astype(jnp.float32) * m, axis=1) / jnp.maximum(
        jnp.sum(m, axis=1), 1.0
    )
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


@partial(jax.jit, static_argnames=("cfg",))
def _encode_jit(params: dict, cfg: EncoderConfig, token_ids: jax.Array, mask: jax.Array):
    return encode(params, cfg, token_ids, mask)


@partial(jax.jit, static_argnames=("cfg",))
def _encode_ids_jit(params: dict, cfg: EncoderConfig, token_ids: jax.Array):
    """ids-only forward: the mask is recovered on device as ``ids != 0``
    (tokenizer contract: pad id is 0 and no real token maps to 0), and narrow
    int dtypes (int16 from the hash tokenizer) widen on device — so the
    host→device transfer is a single small integer array."""
    mask = token_ids != 0
    return encode(params, cfg, token_ids.astype(jnp.int32), mask)


# device profiling plane: every encoder launch counts toward the per-callable
# compile/shape telemetry on /status (+/metrics) — see observability/device.py
encode_jit = _dev_prof.traced_jit("encoder.encode", _encode_jit)
encode_ids_jit = _dev_prof.traced_jit("encoder.encode_ids", _encode_ids_jit)


def contrastive_loss(params, cfg, tok_a, mask_a, tok_b, mask_b, temperature=0.05):
    """Symmetric InfoNCE over in-batch negatives (f32 logits)."""
    za = encode(params, cfg, tok_a, mask_a)
    zb = encode(params, cfg, tok_b, mask_b)
    logits = za @ zb.T / temperature
    labels = jnp.arange(logits.shape[0])
    la = -jnp.mean(jax.nn.log_softmax(logits, axis=1)[labels, labels])
    lb = -jnp.mean(jax.nn.log_softmax(logits, axis=0)[labels, labels])
    return 0.5 * (la + lb)


def contrastive_train_step(params, cfg, opt_state, batch, lr=1e-4):
    """One SGD-with-momentum step on the InfoNCE loss. batch = (tok_a, mask_a,
    tok_b, mask_b). Returns (params, opt_state, loss)."""
    loss, grads = jax.value_and_grad(contrastive_loss)(
        params, cfg, batch[0], batch[1], batch[2], batch[3]
    )
    new_opt = jax.tree.map(lambda m, g: 0.9 * m + g, opt_state, grads)
    new_params = jax.tree.map(lambda p, m: p - lr * m, params, new_opt)
    return new_params, new_opt, loss


class HashTokenizer:
    """Deterministic hashing tokenizer: whitespace+punct split, token → bucket via
    stable hash. No external vocab files; good enough for indexing/recall pipelines
    and fully reproducible across hosts (SURVEY §7.3 byte-identical answers).

    The per-doc loop runs in C when the toolchain is available
    (``native/pwtok.c``, bit-identical mirror of ``_tok`` for ASCII text) —
    pure-Python per-word hashing was the round-3 ingest bottleneck.
    Emits int16 ids when the vocab fits (halves the host→device transfer);
    id 0 is reserved for padding, so ``ids != 0`` recovers the mask on device.
    """

    #: id 0 is reserved for padding by construction (real ids are >= 1)
    pad_id_zero = True

    def __init__(self, vocab_size: int = 32768, max_len: int = 128):
        self.vocab_size = vocab_size
        self.max_len = max_len

    def _tok(self, text: str) -> list[int]:
        import re

        words = re.findall(r"[A-Za-z0-9]+|[^\sA-Za-z0-9]", text.lower())
        out = []
        for w in words[: self.max_len]:
            h = 1469598103934665603
            for ch in w.encode():
                h = ((h ^ ch) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
            out.append(3 + h % (self.vocab_size - 3))  # 0=pad, 1=cls, 2=sep
        return out

    def _tok_batch(self, texts: list[str]) -> tuple[np.ndarray, np.ndarray]:
        """(word_ids [N, max_len] int32, lens [N]) via the C kernel with a
        Python fallback for non-ASCII rows (and for a missing compiler)."""
        if _pwtok_native is not None:
            arr = np.empty(len(texts), dtype=object)
            arr[:] = texts
            cids, lens = _pwtok_native.hash_tokenize(arr, self.vocab_size, self.max_len)
            fallback = np.nonzero(lens < 0)[0]
            for i in fallback:
                t = self._tok(texts[i])
                lens[i] = len(t)
                cids[i, : len(t)] = t
            return cids, lens
        cids = np.zeros((len(texts), self.max_len), dtype=np.int32)
        lens = np.zeros(len(texts), dtype=np.int32)
        for i, text in enumerate(texts):
            t = self._tok(text)
            lens[i] = len(t)
            cids[i, : len(t)] = t
        return cids, lens

    def __call__(self, texts: list[str]) -> tuple[np.ndarray, np.ndarray]:
        # pad sequence length to a power-of-two bucket so jitted callers see a small
        # closed set of shapes (compile-cache discipline, ops/microbatch.py)
        from pathway_tpu.ops.microbatch import LENGTH_MAX_BUCKET, bucket_size

        cids, lens = self._tok_batch(texts)
        L = min(
            self.max_len,
            bucket_size(
                int(lens.max(initial=0)) + 1, min_bucket=16, max_bucket=LENGTH_MAX_BUCKET
            ),
        )
        n = len(texts)
        dtype = np.int16 if self.vocab_size <= 32768 else np.int32
        ids = np.zeros((n, L), dtype=dtype)
        ids[:, 0] = 1  # [CLS]
        keep = np.minimum(lens, L - 1)
        body = np.arange(L - 1)[None, :] < keep[:, None]
        ids[:, 1:] = np.where(body, cids[:, : L - 1], 0).astype(dtype)
        mask = ids != 0
        return ids, mask


class WordPieceTokenizer:
    """Greedy longest-match-first WordPiece (the BERT/MiniLM tokenizer;
    reference embedders tokenize through HuggingFace — ``embedders.py:340``).
    Vocabulary loads from a standard ``vocab.txt`` (one token per line,
    ``##``-prefixed continuations)."""

    def __init__(
        self,
        vocab: dict,
        max_len: int = 128,
        lowercase: bool = True,
        unk_token: str = "[UNK]",
        cls_token: str = "[CLS]",
        sep_token: str = "[SEP]",
        max_word_chars: int = 100,
    ):
        self.vocab = vocab
        self.max_len = max_len
        self.lowercase = lowercase
        self.unk_id = vocab[unk_token]
        self.cls_id = vocab[cls_token]
        self.sep_id = vocab[sep_token]
        self.max_word_chars = max_word_chars
        # ids-only device transfer is safe only if vocab slot 0 is the pad
        # token (standard for BERT vocabs); otherwise the mask must ship
        self.pad_id_zero = vocab.get("[PAD]", -1) == 0

    @classmethod
    def from_vocab_file(cls, path: str, **kwargs) -> "WordPieceTokenizer":
        vocab: dict = {}
        with open(path, encoding="utf-8") as f:
            for i, line in enumerate(f):
                vocab[line.rstrip("\r\n")] = i
        return cls(vocab, **kwargs)

    def _basic(self, text: str) -> list:
        if self.lowercase:
            import unicodedata

            text = unicodedata.normalize("NFD", text.lower())
            text = "".join(c for c in text if unicodedata.category(c) != "Mn")
        out: list = []
        word = []
        for ch in text:
            if ch.isspace():
                if word:
                    out.append("".join(word))
                    word = []
            elif not ch.isalnum():
                if word:
                    out.append("".join(word))
                    word = []
                out.append(ch)
            else:
                word.append(ch)
        if word:
            out.append("".join(word))
        return out

    def _wordpiece(self, word: str) -> list:
        if len(word) > self.max_word_chars:
            return [self.unk_id]
        ids: list = []
        start = 0
        while start < len(word):
            end = len(word)
            cur = None
            while start < end:
                piece = word[start:end]
                if start > 0:
                    piece = "##" + piece
                if piece in self.vocab:
                    cur = self.vocab[piece]
                    break
                end -= 1
            if cur is None:
                return [self.unk_id]  # any unmatchable span voids the word
            ids.append(cur)
            start = end
        return ids

    def _tok(self, text: str) -> list:
        ids: list = []
        for word in self._basic(text):
            ids.extend(self._wordpiece(word))
            if len(ids) >= self.max_len - 2:
                break
        return ids[: self.max_len - 2]

    def __call__(self, texts: list) -> tuple:
        toks = [[self.cls_id] + self._tok(t) + [self.sep_id] for t in texts]
        from pathway_tpu.ops.microbatch import LENGTH_MAX_BUCKET, bucket_size

        L = min(
            self.max_len,
            bucket_size(
                max((len(t) for t in toks), default=1),
                min_bucket=16,
                max_bucket=LENGTH_MAX_BUCKET,
            ),
        )
        ids = np.zeros((len(toks), L), dtype=np.int32)
        mask = np.zeros((len(toks), L), dtype=bool)
        for i, t in enumerate(toks):
            t = t[:L]
            ids[i, : len(t)] = t
            mask[i, : len(t)] = True
        return ids, mask


class JaxSentenceEncoder:
    """Batched text → embedding model: tokenizer + jitted transformer forward.

    The drop-in compute backend for the xpack embedder UDFs; one call embeds a whole
    microbatch (contrast: reference embeds per row).
    """

    def __init__(
        self,
        cfg: EncoderConfig | None = None,
        seed: int = 0,
        mesh: Mesh | None = None,
        params: dict | None = None,
        tokenizer: Any = None,
        param_dtype: Any = None,
    ):
        self.cfg = cfg or EncoderConfig()
        self.params = params if params is not None else init_params(self.cfg, jax.random.PRNGKey(seed))
        if param_dtype is not None:
            # store matrices in the compute dtype (bf16): halves HBM weight
            # traffic and skips the per-call f32→bf16 casts; norms/biases stay f32
            self.params = jax.tree.map(
                lambda p: p.astype(param_dtype) if getattr(p, "ndim", 0) >= 2 else p,
                self.params,
            )
        self.tokenizer = tokenizer or HashTokenizer(self.cfg.vocab_size, self.cfg.max_len)
        if mesh is not None:
            # tensor-parallel runs must keep the GSPMD-partitionable XLA
            # attention: pallas_call has no sharding rule for the Megatron
            # column-split of wqkv
            self.cfg = self.cfg._replace(pallas_attention=False)
            self.params = jax.tree.map(
                lambda p, s: jax.device_put(p, s),
                self.params,
                param_shardings(self.cfg, mesh),
            )
        # memory attribution: encoder weights show up as
        # pathway_device_bytes{component="encoder_params"} while this
        # instance lives (weakly registered — no lifetime coupling)
        _dev_prof.register_memory(
            self, "encoder_params", lambda enc: enc.param_bytes()
        )

    @property
    def dimension(self) -> int:
        return self.cfg.d_model

    def param_bytes(self) -> int:
        return int(sum(p.nbytes for p in jax.tree.leaves(self.params)))

    def _note_launch(self, ids, mask=None) -> int:
        """Padding-waste accounting for one encoder launch over the PADDED
        token grid the device actually runs; returns the real tokens."""
        real = int(np.count_nonzero(np.asarray(mask if mask is not None else ids)))
        stats = _dev_prof.stats()
        if stats.enabled:
            stats.note_pad_tokens("encoder", real, int(ids.size) - real)
        return real

    def encode_texts(self, texts: list[str]) -> np.ndarray:
        if not texts:
            return np.zeros((0, self.cfg.d_model), dtype=np.float32)
        return _dev_prof.fetch(self.encode_texts_device(texts), "encoder")

    def encode_texts_device(self, texts: list[str]) -> jax.Array:
        """Like ``encode_texts`` but returns the device array without syncing —
        chain into device-consuming ops (e.g. ``BruteForceKnnIndex.
        add_batch_device``) to keep a whole ingest pipeline async.

        When the tokenizer declares ``pad_id_zero`` (pad id is 0 and no real
        token maps to 0 — true for the hash tokenizer and for WordPiece vocabs
        whose slot 0 is [PAD]), only the (narrow-int) id array crosses to the
        device and the mask is re-derived there; otherwise the tokenizer's own
        mask is honored and shipped alongside."""
        tok = _obs.begin("embed/tokenize")
        ids, mask = self.tokenizer(texts)
        real = self._note_launch(ids, mask)
        if tok is not None:
            _obs.end(
                tok,
                {
                    "pathway.rows": len(texts),
                    "pathway.real_tokens": real,
                    "pathway.padded_len": int(ids.shape[1]),
                },
            )
        if getattr(self.tokenizer, "pad_id_zero", False):
            return encode_ids_jit(self.params, self.cfg, _dev_prof.put(ids, "encoder.ids"))
        return encode_jit(
            self.params, self.cfg, _dev_prof.put(ids, "encoder.ids", jnp.int32), mask
        )

    def encode_tokens(self, ids: np.ndarray, mask: np.ndarray) -> np.ndarray:
        self._note_launch(ids, mask)
        return _dev_prof.fetch(encode_jit(self.params, self.cfg, ids, mask), "encoder")

    def encode_ids_device(self, ids: np.ndarray | jax.Array) -> jax.Array:
        """Pre-tokenized ids (pad id 0) → embeddings, fully on device."""
        if isinstance(ids, np.ndarray):
            self._note_launch(ids)
        return encode_ids_jit(self.params, self.cfg, ids)

    @classmethod
    def from_pretrained(
        cls,
        path: str,
        *,
        max_len: int | None = None,
        mesh: Mesh | None = None,
        dtype: Any = None,
    ) -> "JaxSentenceEncoder":
        """Load a HuggingFace BERT/MiniLM checkpoint directory (``config.json``
        + ``model.safetensors``/``pytorch_model.bin`` [+ ``vocab.txt``]) into
        the exact-BERT forward path, reproducing the reference
        SentenceTransformerEmbedder's embeddings on TPU
        (``xpacks/llm/embedders.py:340-398``)."""
        import json
        import os

        with open(os.path.join(path, "config.json"), encoding="utf-8") as f:
            hf = json.load(f)
        cfg = EncoderConfig(
            vocab_size=hf["vocab_size"],
            d_model=hf["hidden_size"],
            n_heads=hf["num_attention_heads"],
            n_layers=hf["num_hidden_layers"],
            d_ff=hf["intermediate_size"],
            max_len=min(hf.get("max_position_embeddings", 512), max_len or 512),
            dtype=dtype if dtype is not None else jnp.float32,
            arch="bert",
            ln_eps=hf.get("layer_norm_eps", 1e-12),
        )
        sd = _load_state_dict(path)

        def get(name):
            for prefix in ("", "bert."):
                if prefix + name in sd:
                    return jnp.asarray(np.asarray(sd[prefix + name]), dtype=jnp.float32)
            raise KeyError(f"missing checkpoint tensor {name!r}")

        params: dict = {
            "embed": get("embeddings.word_embeddings.weight"),
            "pos": get("embeddings.position_embeddings.weight"),
            "tok_type": get("embeddings.token_type_embeddings.weight"),
            "emb_ln": {
                "g": get("embeddings.LayerNorm.weight"),
                "b": get("embeddings.LayerNorm.bias"),
            },
            "layers": [],
            "ln_f": {"g": jnp.ones((cfg.d_model,)), "b": jnp.zeros((cfg.d_model,))},
        }
        for i in range(cfg.n_layers):
            pre = f"encoder.layer.{i}."
            wq = get(pre + "attention.self.query.weight").T
            wk = get(pre + "attention.self.key.weight").T
            wv = get(pre + "attention.self.value.weight").T
            bq = get(pre + "attention.self.query.bias")
            bk = get(pre + "attention.self.key.bias")
            bv = get(pre + "attention.self.value.bias")
            params["layers"].append(
                {
                    "wqkv": jnp.concatenate([wq, wk, wv], axis=1),
                    "bqkv": jnp.concatenate([bq, bk, bv]),
                    "wo": get(pre + "attention.output.dense.weight").T,
                    "bo": get(pre + "attention.output.dense.bias"),
                    "ln1": {
                        "g": get(pre + "attention.output.LayerNorm.weight"),
                        "b": get(pre + "attention.output.LayerNorm.bias"),
                    },
                    "w1": get(pre + "intermediate.dense.weight").T,
                    "b1": get(pre + "intermediate.dense.bias"),
                    "w2": get(pre + "output.dense.weight").T,
                    "b2": get(pre + "output.dense.bias"),
                    "ln2": {
                        "g": get(pre + "output.LayerNorm.weight"),
                        "b": get(pre + "output.LayerNorm.bias"),
                    },
                }
            )
        lowercase = hf.get("do_lower_case", True)
        vocab_path = os.path.join(path, "vocab.txt")
        tok_json = os.path.join(path, "tokenizer.json")
        tokenizer: Any
        if os.path.exists(vocab_path):
            tokenizer = WordPieceTokenizer.from_vocab_file(
                vocab_path, max_len=cfg.max_len, lowercase=lowercase
            )
        elif os.path.exists(tok_json):
            with open(tok_json, encoding="utf-8") as f:
                vocab = json.load(f)["model"]["vocab"]
            tokenizer = WordPieceTokenizer(
                vocab, max_len=cfg.max_len, lowercase=lowercase
            )
        else:
            import warnings

            warnings.warn(
                f"{path!r} has neither vocab.txt nor tokenizer.json: falling "
                "back to the hash tokenizer — embeddings will NOT match the "
                "reference model for these weights",
                stacklevel=2,
            )
            tokenizer = HashTokenizer(cfg.vocab_size, cfg.max_len)
        return cls(cfg, mesh=mesh, params=params, tokenizer=tokenizer)


def _load_state_dict(path: str) -> dict:
    import os

    st_path = os.path.join(path, "model.safetensors")
    if os.path.exists(st_path):
        from safetensors.numpy import load_file

        return load_file(st_path)
    bin_path = os.path.join(path, "pytorch_model.bin")
    if os.path.exists(bin_path):
        import torch

        sd = torch.load(bin_path, map_location="cpu", weights_only=True)
        return {k: v.numpy() for k, v in sd.items()}
    raise FileNotFoundError(
        f"no model.safetensors or pytorch_model.bin under {path!r}"
    )


def encoder_flops_per_doc(cfg: EncoderConfig, seq_len: int) -> float:
    """Matmul FLOPs of one forward pass per document (MFU accounting)."""
    d, f, L = cfg.d_model, cfg.d_ff, seq_len
    per_layer = (
        2 * L * d * (3 * d)      # qkv projection
        + 2 * L * d * d          # output projection
        + 2 * 2 * L * L * d      # attention scores + context
        + 2 * L * d * f * 2      # feed-forward up + down
    )
    return float(cfg.n_layers * per_layer)
