"""Model registry: name → loaded, servable ModelBundle.

This is the TPU-native answer to the reference's ``ModelWrapper.load()``
(BASELINE.json:5): selecting a model materializes its params as a JAX
pytree (from a converted checkpoint when ``MODEL_PATH`` is set, else
deterministic random init — no network/HF hub here, SURVEY.md §7.1),
binds host-side pre/post-processing, and exposes jittable device
functions for the engine to compile per shape bucket.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable

import numpy as np

from ..runtime.device import DtypePolicy
from . import bert as bert_mod
from . import resnet as resnet_mod
from . import t5 as t5_mod
from .preprocess import decode_image_u8, load_labels, normalize_imagenet, softmax_np, topk_np
from .tokenizer import build_tokenizer

log = logging.getLogger(__name__)

KIND_IMAGE = "image_classification"
KIND_TEXT = "text_classification"
KIND_SEQ2SEQ = "seq2seq"


@dataclasses.dataclass
class ModelBundle:
    """Everything the engine/scheduler/API need to serve one model."""

    name: str
    kind: str
    cfg: Any
    params: Any  # device pytree
    policy: DtypePolicy
    tokenizer: Any | None
    labels: list[str] | None
    # Jittable: (params, *batch arrays) -> outputs. Engine owns jit+buckets.
    forward: Callable | None
    # seq2seq trio (jittable): encode, init_decode_state, generate_chunk.
    encode_fn: Callable | None = None
    init_state_fn: Callable | None = None
    generate_chunk_fn: Callable | None = None
    image_size: int = 224
    # Optional engine-placement override: () -> ReplicaSet-like. Lets a
    # model pick a non-default sharding (bert-long uses SeqParallelSet:
    # sequence axis over ('sp',) for ring attention).
    make_placement: Callable | None = None
    # Hard cap on tokenized prompt length (decoder-only models must
    # leave position-table room for generation — jnp.take would clamp
    # out-of-range positions silently otherwise).
    max_prompt_len: int | None = None
    # Whether this family consumed cfg.prompt_prefix (cached system-
    # prompt KV); build_model rejects the knob when unsupported.
    supports_prefix: bool = False
    # Speculative decoding (generative families; models/spec.py):
    # init_spec_fn(state, ids, mask, prefix_ids=None) -> SpecState
    # builds the drafting history (``prefix_ids`` arrives on
    # per-request prefix-cache hits).  Decoder-only families use
    # spec.make_init_spec_fn (the contract's one implementation for
    # the GPTState layout); encoder-decoders need their own history
    # layout — t5.init_spec_state prepends the ENCODER ids so lookup
    # drafts from the document.  spec_chunk_fn(params, spec_state,
    # n_verify, spec_k, sample=False) -> (SpecState, out [B,nv,K+1],
    # n_emit [B,nv]) runs n_verify draft→verify rounds in one dispatch;
    # ``sample`` (static) turns on rejection-sampling acceptance for
    # temperature>0 rows.  None = family does not support SPEC_DECODE.
    init_spec_fn: Callable | None = None
    spec_chunk_fn: Callable | None = None
    # Block-paged KV decode (PAGED_KV=1, decoder-only families):
    # paged_chunk_fn(params, paged_state, table, n_steps, sample=False)
    # -> (paged_state, tokens) runs n_steps decode steps reading and
    # writing K/V through the traced block table (models/gpt.PagedState
    # layout; engine/kv_blocks.py owns the host-side tables).  None =
    # family does not support PAGED_KV.
    paged_chunk_fn: Callable | None = None
    # Chunked prefill (PREFILL_CHUNK, decoder-only families;
    # docs/chunked-prefill.md).  empty_state_fn(params, batch, s_total,
    # max_len) -> all-dead decode state sized for a chunked prefill;
    # prefill_chunk_fn(params, state, ids, mask, start) consumes one
    # [B, C] prompt window at absolute position ``start`` (traced);
    # paged_prefill_chunk_fn(params, paged_state, table_rows [B, T],
    # ids [B, C], mask [B, C], starts [B]) is the PAGED_KV variant: one
    # window each of B different prompts in one dispatch, each written
    # straight into its own stream's pool blocks.  None = family does
    # not support PREFILL_CHUNK (encoder-decoders prefill the decoder
    # from a start token — there is no prompt to chunk).
    empty_state_fn: Callable | None = None
    prefill_chunk_fn: Callable | None = None
    paged_prefill_chunk_fn: Callable | None = None
    # Every position's next-token logits, jittable: (params, input_ids
    # [B, S], attention_mask [B, S]) -> [B, S, V] — the non-generative
    # forward of a decoder family, what a reference comparison holds to
    # its own logits where served tokens cannot tell a rule apart.
    logits_fn: Callable | None = None

    # -- host-side single-item pre/post ------------------------------------
    def preprocess(self, item: "RawItem") -> dict[str, np.ndarray]:
        if self.kind == KIND_IMAGE:
            if item.image is None:
                raise ValueError("this model expects an image payload")
            # uint8 on the wire; normalization happens in-jit on device.
            return {"image": decode_image_u8(item.image, self.image_size)}
        if item.text is None:
            raise ValueError("this model expects a text payload")
        if self.max_prompt_len is not None:
            max_len = self.max_prompt_len
        else:
            max_len = self.cfg.max_position if hasattr(self.cfg, "max_position") else 512
        ids, mask = self.tokenizer.encode(item.text, max_len)
        n = int(mask.sum())
        feats = {"input_ids": ids[:n], "length": np.int32(n)}
        if self.kind == KIND_SEQ2SEQ:
            if item.temperature > 0.0:
                feats["temperature"] = float(item.temperature)
                feats["top_k"] = int(item.top_k)
                feats["top_p"] = float(item.top_p)
                if item.seed is not None:
                    feats["seed"] = int(item.seed)
            if item.max_tokens is not None:
                # Scheduler-visible budget: the decode loop stops
                # spending chunks on a row once it is reached.
                feats["max_tokens"] = int(item.max_tokens)
        return feats

    def postprocess(self, row: np.ndarray) -> dict:
        if self.kind == KIND_IMAGE:
            idx, probs = topk_np(row[None], k=5)
            top = [
                {
                    "class_id": int(i),
                    "score": round(float(p), 6),
                    **({"label": self.labels[int(i)]} if self.labels else {}),
                }
                for i, p in zip(idx[0], probs[0])
            ]
            return {"prediction": top[0], "topk": top}
        if self.kind == KIND_TEXT:
            probs = softmax_np(row)
            label_id = int(np.argmax(probs))
            return {
                "prediction": {
                    "label_id": label_id,
                    **({"label": self.labels[label_id]} if self.labels else {}),
                    "score": round(float(probs[label_id]), 6),
                },
                "probs": [round(float(p), 6) for p in probs],
            }
        # seq2seq: row is a token id vector.
        return {"prediction": {"text": self.tokenizer.decode(row)}}


@dataclasses.dataclass
class RawItem:
    """One unparsed /predict payload.

    Sampling knobs apply to generative (seq2seq/causal-LM) models only;
    temperature 0 = greedy (the default).  Unseeded sampled requests
    draw a fresh seed per request."""

    text: str | None = None
    image: bytes | None = None
    stream: bool = False
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int | None = None
    # Generation stops after this many tokens (None = the server's
    # MAX_DECODE_LEN budget) or when any stop string appears.
    max_tokens: int | None = None
    stop: tuple[str, ...] = ()


# ---------------------------------------------------------------------------
# builders


def _load_or_init(name: str, model_path: str | None, init_fn, converter):
    """Load converted checkpoint if given, else deterministic random init."""
    import jax

    if model_path:
        from .checkpoint import load_pytree

        log.info("loading %s checkpoint from %s", name, model_path)
        return load_pytree(model_path, converter)
    log.info("no MODEL_PATH for %s — deterministic random init", name)
    return init_fn(jax.random.PRNGKey(0))



def _maybe_quantize(params, svc_cfg):
    """Apply QUANTIZE=int8 weight-only quantization after dtype cast
    (scales stay f32; see models/quant.py)."""
    mode = getattr(svc_cfg, "quantize", None)
    if not mode:
        return params
    from .quant import quantize_pytree

    return quantize_pytree(params, mode)


def _load_weights(name: str, svc_cfg, policy: DtypePolicy, init_fn,
                  converter, check: Callable | None = None):
    """The serving tree under the boot phase ``boot/weights``: read the
    converted checkpoint or draw the seeded init (``check`` sees that
    tree and raises on one it cannot serve), cast to the serving dtype,
    quantize (QUANTIZE).  The row carries the tree's size."""
    import jax

    from ..utils import tracing
    from .common import cast_pytree

    with tracing.boot_phase("boot/weights") as ph:
        params = _load_or_init(name, svc_cfg.model_path, init_fn, converter)
        if check is not None:
            check(params)
        params = cast_pytree(params, policy.param_jnp)
        params = _maybe_quantize(params, svc_cfg)
        leaves = jax.tree.leaves(params)
        ph.set(parameters=sum(int(x.size) for x in leaves),
               bytes=sum(int(x.nbytes) for x in leaves))
    return params


def _tokenizer(build: Callable, *args, **kwargs):
    """``build(*args, **kwargs)`` under the boot phase ``boot/tokenizer``."""
    from ..utils import tracing

    with tracing.boot_phase("boot/tokenizer"):
        return build(*args, **kwargs)


def _attach_prompt_prefix(params, tokenizer, svc_cfg, compute_fn,
                          max_positions: int) -> int:
    """Cache a shared system-prompt prefix's KV into the params pytree
    (``__prefix__``) — computed once here (one jitted dispatch), then
    placed/sharded/traced like weights.  Returns the prefix token count
    (0 = no prefix configured)."""
    prefix = getattr(svc_cfg, "prompt_prefix", None)
    if not prefix:
        return 0
    # TP composes: TensorParallelSet replicates spec-unknown subtrees
    # (the prefix KV) across the mesh — correct, just unsharded.
    import jax

    ids, mask = tokenizer.encode(prefix, max_positions)
    n = int(mask.sum())
    # The request tokenizer may append terminal specials (byte/SP
    # fallbacks add eos; WordPiece adds [SEP]).  Baked into the MIDDLE
    # of every served context, an EOS acts as a document separator and
    # severs the prefix from the prompt — strip terminal specials, keep
    # any leading BOS.
    terminal = {
        int(t) for t in (
            getattr(tokenizer, "eos_id", None), getattr(tokenizer, "sep_id", None)
        ) if t is not None
    }
    while n > 0 and int(ids[n - 1]) in terminal:
        n -= 1
    if n == 0:
        raise ValueError("PROMPT_PREFIX tokenized to zero (non-special) tokens")
    params["__prefix__"] = jax.jit(compute_fn)(params, ids[:n])
    log.info("cached prompt prefix: %d tokens", n)
    return n


def _decode_position_budget(svc_cfg, max_position: int, p_len: int,
                            family: str) -> int:
    """Shared decoder-position arithmetic: prefix + prompt + decode must
    fit inside ``max_position`` (jnp.take would silently clamp past it).
    Returns the max prompt length; raises when the budget is impossible
    or a configured seq bucket exceeds it."""
    import math as _math

    chunk = max(1, int(getattr(svc_cfg, "stream_chunk_tokens", 4)))
    decode_budget = int(_math.ceil(svc_cfg.max_decode_len / chunk) * chunk)
    if decode_budget + p_len >= max_position:
        raise ValueError(
            f"MAX_DECODE_LEN(+chunk rounding)={decode_budget} plus prefix "
            f"{p_len} leaves no room for a prompt within {family}'s "
            f"{max_position} positions"
        )
    max_prompt = max_position - decode_budget - p_len
    bad = [s for s in svc_cfg.seq_buckets if s > max_prompt]
    if bad:
        raise ValueError(
            f"SEQ_BUCKETS {bad} exceed {family}'s position budget: max "
            f"prompt = {max_position} - {decode_budget} decode - {p_len} "
            f"prefix = {max_prompt}"
        )
    return max_prompt


def _refuse_cache_readers(svc_cfg, what: str, whys: dict) -> None:
    """Raise for the first configured reader of the KV cache that a llama
    config with ``what`` cannot be served through (``whys``: knob -> why)."""
    on = {
        "PAGED_KV=0": not getattr(svc_cfg, "paged_kv", False),
        "SPEC_DECODE": getattr(svc_cfg, "spec_decode", None),
        "QUANT_KV": getattr(svc_cfg, "quant_kv", None),
        "PREFIX_CACHE": getattr(svc_cfg, "prefix_cache", False),
        "PROMPT_PREFIX": getattr(svc_cfg, "prompt_prefix", None),
        "KV_HOST_BUDGET_MB": getattr(svc_cfg, "kv_host_budget_mb", 0),
        "KV_DISK_BUDGET_MB": getattr(svc_cfg, "kv_disk_budget_mb", 0),
    }
    for knob, why in whys.items():
        if on[knob]:
            raise ValueError(
                f"{knob} is not supported for a llama config with {what}: {why}"
            )


def _pallas_knobs(svc_cfg) -> dict:
    """Kernel-selection knobs every decoder-only family plumbs into its
    (frozen) model config at build time (docs/kernel_tuning.md):
    ``PALLAS_VARIANT`` pins one autotuner variant (validated here — a
    typo'd pin must fail at boot, not at first trace) and
    ``PALLAS_INTERPRET`` runs the kernels in interpret mode, which also
    lifts the TPU backend gate so CPU CI/serving can exercise the real
    kernel path end-to-end."""
    out: dict = {}
    interp = bool(getattr(svc_cfg, "pallas_interpret", False))
    if interp:
        out["pallas_interpret"] = True
    pin = getattr(svc_cfg, "pallas_variant", None)
    if pin:
        from ..ops.paged_attention import parse_variant

        parse_variant(pin)
        out["pallas_variant"] = pin
    # TP width rides in the frozen model config too: kernel call sites
    # are pure functions that decide shard_map wrapping at trace time,
    # and the autotuner keys TP entries apart.  TP<=1 sets nothing —
    # the config (and every executable keyed on it) stays bit-identical
    # to pre-TP builds.
    tp = int(getattr(svc_cfg, "tp", 0) or 0)
    if tp > 1:
        out["tp"] = tp
    return out


def _pallas_backend_ok(svc_cfg) -> bool:
    """The fused decode kernels lower on TPU only; interpret mode is
    the explicit escape hatch (CPU CI, the pallas_ab bench)."""
    if getattr(svc_cfg, "pallas_interpret", False):
        return True
    import jax

    return jax.default_backend() == "tpu"


def _pallas_decode_unavailable(why: str):
    """An explicit USE_PALLAS_DECODE=1 that cannot be honoured fails
    the boot: serving the jnp path under a warning would hide which
    path a run exercised."""
    return RuntimeError(
        f"USE_PALLAS_DECODE=1 cannot be honoured: {why} (unset the knob "
        "to follow the backend, or set PALLAS_INTERPRET=1 for the CPU "
        "interpret path)"
    )


def _tp_placement(svc_cfg, model_cfg, family: str, devices=None):
    """TP=<n> → a TensorParallelSet factory over a ('replica','tp')
    mesh with the family's Megatron param spec; None when TP is off.

    ``devices`` (global device ids) places the group on a specific
    carve instead of the visible-device prefix — the multi-chip fleet's
    per-replica placement path (engine/fleet.py).

    Mutually exclusive with QUANTIZE: int8 leaves are {"q8","scale"}
    dicts the per-leaf PartitionSpec tree cannot describe.
    """
    tp = int(getattr(svc_cfg, "tp", 0) or 0)
    if tp <= 1:
        return None
    if getattr(svc_cfg, "quantize", None):
        raise ValueError(
            "TP and QUANTIZE cannot combine (quantized leaves are "
            "{'q8','scale'} subtrees the TP param spec cannot shard); "
            "pick one"
        )
    heads = int(getattr(model_cfg, "num_heads", 0) or 0)
    kvh = int(getattr(model_cfg, "num_kv_heads", heads) or heads)
    if heads and (heads % tp or kvh % tp):
        raise ValueError(
            f"TP={tp} must divide attention heads evenly "
            f"(num_heads={heads}, kv_heads={kvh}): q/k/v shards and the "
            "KV cache's heads axis split over the 'tp' mesh axis"
        )
    from ..parallel import TensorParallelSet
    from ..parallel.tp import PARAM_SPECS
    from ..parallel.tpserve import serving_tp_mesh

    spec = PARAM_SPECS[family](model_cfg)
    # REPLICAS=0 (unset) pins the mesh replica axis to 1: TP=<n> claims
    # exactly n devices.  The 2-D auto-fill (every leftover device into
    # the replica axis) would silently turn TP=2 on an 8-device host
    # into a 4x2 DP x TP grid — which the paged block pool rejects
    # (no batch axis to shard) and which the fleet layer already covers
    # with separate engines.  An explicit REPLICAS>1 still composes for
    # contiguous-KV serving.  The mesh comes from the serving-mesh
    # cache (same structural mesh make_replica_tp_mesh built), so the
    # engine placement and every trace-time shard_map reconstruction
    # share ONE object per (tp, replicas, devices) — multi-chip fleet
    # groups pass their carved device ids through ``devices``.
    mesh = serving_tp_mesh(
        tp, int(getattr(svc_cfg, "replicas", 0) or 1), group=devices
    )
    return lambda: TensorParallelSet(mesh, spec)


def _build_resnet(svc_cfg, policy: DtypePolicy) -> ModelBundle:
    from ..convert import resnet_state_to_pytree

    cfg = resnet_mod.ResNetConfig()
    params = _load_weights("resnet50", svc_cfg, policy,
                           functools.partial(resnet_mod.init_params, cfg=cfg),
                           resnet_state_to_pytree)

    def forward(p, images):
        # images arrive uint8; normalize on device, then cast for the MXU.
        x = normalize_imagenet(images)
        return resnet_mod.apply(p, cfg, x.astype(policy.compute_jnp))

    return ModelBundle(
        name="resnet50",
        kind=KIND_IMAGE,
        cfg=cfg,
        params=params,
        policy=policy,
        tokenizer=None,
        labels=load_labels(getattr(svc_cfg, "labels_path", None)),
        forward=forward,
        image_size=cfg.image_size,
    )


def _build_bert(svc_cfg, policy: DtypePolicy) -> ModelBundle:
    from ..convert import bert_state_to_pytree

    cfg = bert_mod.BertConfig()
    params = _load_weights("bert-base", svc_cfg, policy,
                           functools.partial(bert_mod.init_params, cfg=cfg),
                           bert_state_to_pytree)

    # TP=<n>: Megatron-shard the params over a ('replica','tp') mesh.
    make_placement = _tp_placement(svc_cfg, cfg, "bert")

    # Decide the Pallas fused-attention path once, at serving-build
    # time: inference-only call site, so the kernel's lack of VJP and
    # sharding rules never leaks into training/tp consumers.  The max
    # seq bucket gates the default (single-block VMEM regime); TP
    # forces the jnp path (the kernel has no sharding rules).
    from ..ops.attention import use_pallas_attention

    use_pallas = make_placement is None and use_pallas_attention(
        max_seq=max(svc_cfg.seq_buckets)
    )

    def forward(p, input_ids, attention_mask):
        return bert_mod.classify(
            p, cfg, input_ids, attention_mask,
            dtype=policy.compute_jnp, use_pallas=use_pallas,
        )

    return ModelBundle(
        name="bert-base",
        kind=KIND_TEXT,
        cfg=cfg,
        params=params,
        policy=policy,
        tokenizer=_tokenizer(build_tokenizer, svc_cfg.tokenizer_path, for_t5=False),
        labels=load_labels(getattr(svc_cfg, "labels_path", None)),
        forward=forward,
        make_placement=make_placement,
    )


def _build_bert_long(svc_cfg, policy: DtypePolicy) -> ModelBundle:
    """Long-context BERT classifier served with ring attention.

    The sequence axis shards over an ``('sp',)`` mesh
    (``parallel.SeqParallelSet``); every encoder layer's attention runs
    as a ppermute ring (``parallel/ring.py``), so per-device score
    memory is O((S/n)²) and S scales with the mesh instead of a single
    chip's VMEM/HBM.  Capability beyond the reference (SURVEY.md §2
    lists no long-context machinery); the serving stack — buckets,
    batcher, API — is unchanged.  SP=<width> picks the mesh size
    (0 = all visible devices); every seq bucket must divide by it.
    """
    from ..convert import bert_state_to_pytree
    from ..parallel import SeqParallelSet, make_sp_mesh
    from ..parallel.ring import make_ring_attention

    max_pos = max(max(svc_cfg.seq_buckets), 512)
    cfg = bert_mod.BertConfig(max_position=max_pos)

    def covers_buckets(params) -> None:
        # A loaded checkpoint's position table must actually cover the long
        # buckets: jnp.take CLAMPS out-of-range indices, so an undersized
        # table would silently reuse its last row for every position past
        # it — confidently wrong logits, no error. Fail at startup instead.
        pos_rows = params["embeddings"]["position"]["embedding"].shape[0]
        if pos_rows < max_pos:
            raise ValueError(
                f"bert-long needs a position-embedding table with >= {max_pos} "
                f"rows for SEQ_BUCKETS={svc_cfg.seq_buckets}, but the loaded "
                f"checkpoint has {pos_rows}; extend the table (e.g. interpolate) "
                "or lower the buckets"
            )

    params = _load_weights("bert-long", svc_cfg, policy,
                           functools.partial(bert_mod.init_params, cfg=cfg),
                           bert_state_to_pytree, check=covers_buckets)

    # bert-long scales with SP (+ REPLICAS), never TP — fail loudly so
    # a TP knob is not silently swallowed by the SP placement below
    # (build_model's generic guard can't see past make_placement).
    if int(getattr(svc_cfg, "tp", 0) or 0) > 1:
        raise ValueError(
            "TP is not supported for bert-long; scale long-context via "
            "SP=<width> and REPLICAS=<n> (a ('replica','sp') mesh)"
        )

    # REPLICAS>=2 composes batch DP on top of sequence parallelism:
    # a ('replica','sp') mesh whose rows are independent ppermute
    # rings (round-2 verdict: the 1-D sp mesh idled the batch axis).
    from ..parallel import make_replica_sp_mesh

    replicas = int(getattr(svc_cfg, "replicas", 0) or 0)
    if replicas > 1:
        import jax

        sp_width = getattr(svc_cfg, "sp", 0) or max(
            1, len(jax.devices()) // replicas
        )
        mesh = make_replica_sp_mesh(sp_width, replicas)
    else:
        mesh = make_sp_mesh(getattr(svc_cfg, "sp", 0))
    width = int(mesh.shape["sp"])
    bad = [s for s in svc_cfg.seq_buckets if s % width]
    if bad:
        raise ValueError(
            f"SEQ_BUCKETS {bad} not divisible by sp mesh width {width}"
        )
    raw_ring = make_ring_attention(mesh)
    # Pallas hop kernel (VMEM-resident per-hop scores): single-block
    # regime is per-DEVICE, so gate on the largest LOCAL block.
    from ..ops.attention import use_pallas_attention

    use_pallas_ring = use_pallas_attention(
        max_seq=max(svc_cfg.seq_buckets) // width
    )

    def ring(q, k, v, key_mask):
        return raw_ring(q, k, v, key_mask, use_pallas=use_pallas_ring)

    def forward(p, input_ids, attention_mask):
        return bert_mod.classify(
            p, cfg, input_ids, attention_mask,
            dtype=policy.compute_jnp, attn_fn=ring,
        )

    return ModelBundle(
        name="bert-long",
        kind=KIND_TEXT,
        cfg=cfg,
        params=params,
        policy=policy,
        tokenizer=_tokenizer(build_tokenizer, svc_cfg.tokenizer_path, for_t5=False),
        labels=load_labels(getattr(svc_cfg, "labels_path", None)),
        forward=forward,
        make_placement=lambda: SeqParallelSet(mesh),
    )


def _build_t5(svc_cfg, policy: DtypePolicy) -> ModelBundle:
    from ..convert import t5_state_to_pytree

    cfg = t5_mod.T5Config()
    params = _load_weights("t5-small", svc_cfg, policy,
                           functools.partial(t5_mod.init_params, cfg=cfg),
                           t5_state_to_pytree)

    # Same serving-only Pallas opt-in as BERT (the kernel has no VJP;
    # the rel-pos bias rides into the fused kernel as a [1,H,S,S] block).
    from ..ops.attention import use_pallas_attention

    use_pallas = use_pallas_attention(max_seq=max(svc_cfg.seq_buckets))

    def encode_fn(p, input_ids, attention_mask):
        return t5_mod.encode(
            p, cfg, input_ids, attention_mask,
            dtype=policy.compute_jnp, use_pallas=use_pallas,
        )

    def init_state_fn(p, enc_out, enc_mask, max_len: int, sample=None):
        return t5_mod.init_decode_state(p, cfg, enc_out, enc_mask, max_len, sample=sample)

    def generate_chunk_fn(p, state, n_steps: int, sample: bool = False):
        return t5_mod.generate_chunk(p, cfg, state, n_steps, sample)

    # Speculative decoding: summarization quotes its input, so the
    # drafting history is [encoder ids | decoder tokens] and prompt-
    # lookup matches land in the document itself (t5.init_spec_state).
    from . import spec as spec_mod

    def init_spec_fn(state, input_ids, attention_mask, prefix_ids=None):
        return t5_mod.init_spec_state(state, input_ids, attention_mask)

    def spec_chunk_fn(p, spec_state, n_verify: int, spec_k: int,
                      sample: bool = False):
        return spec_mod.spec_chunk(
            p, spec_state, n_verify, spec_k, int(svc_cfg.spec_ngram),
            lambda pp, st, toks: t5_mod.multi_step(pp, cfg, st, toks),
            cfg.eos_id, cfg.pad_id, sample,
        )

    return ModelBundle(
        name="t5-small",
        kind=KIND_SEQ2SEQ,
        cfg=cfg,
        params=params,
        policy=policy,
        tokenizer=_tokenizer(build_tokenizer, svc_cfg.tokenizer_path, for_t5=True),
        labels=None,
        forward=None,
        encode_fn=encode_fn,
        init_state_fn=init_state_fn,
        generate_chunk_fn=generate_chunk_fn,
        init_spec_fn=init_spec_fn,
        spec_chunk_fn=spec_chunk_fn,
    )


def _build_gpt(svc_cfg, policy: DtypePolicy) -> ModelBundle:
    """Decoder-only causal LM (GPT-2), served through the seq2seq
    engine machinery: "encode" passes the prompt through, init prefills
    the KV caches in the same fused dispatch, chunks stream tokens.

    Tokenizer: a real GPT-2 ``vocab.json`` (+ merges.txt) via
    TOKENIZER_PATH; without one, the byte-level fallback is used and
    eos/pad are remapped to its ids so EOS detection stays coherent.
    """
    from ..convert import gpt2_state_to_pytree
    from . import gpt as gpt_mod

    tokenizer = _tokenizer(build_tokenizer, svc_cfg.tokenizer_path, for_t5=True)
    # Fused paged-decode kernel (MHA corner of the llama kernel):
    # USE_PALLAS_DECODE opt-in, TPU-or-interpret gated.  The paged
    # kernel's VMEM footprint is per block-group, not per slab, so the
    # whole-slab fit gate doesn't apply — the autotuner's cost model
    # (ops/autotune.paged_vmem_bytes) bounds each variant instead.
    import os as _os

    gpt_pallas: dict = dict(_pallas_knobs(svc_cfg))
    env_pd = _os.environ.get("USE_PALLAS_DECODE", "").lower()
    if env_pd in ("1", "true", "yes"):
        if not _pallas_backend_ok(svc_cfg):
            raise _pallas_decode_unavailable(
                "backend is not tpu and PALLAS_INTERPRET is off"
            )
        gpt_pallas["pallas_decode"] = True
    cfg = gpt_mod.GPTConfig(
        eos_id=int(tokenizer.eos_id), pad_id=int(tokenizer.pad_id),
        **gpt_pallas,
    )
    # A tokenizer that can emit ids past the checkpoint's embedding
    # table would hit jnp.take's silent clamp (confidently wrong
    # logits, no error) — same failure class as bert-long's position
    # table.  Compare the MAX emittable id, not the vocab count: a
    # sparse/edited vocab.json can have ids far past len(vocab).
    max_id = int(getattr(tokenizer, "max_token_id",
                         getattr(tokenizer, "vocab_size", 1) - 1))
    if max_id >= cfg.vocab_size:
        raise ValueError(
            f"tokenizer at {svc_cfg.tokenizer_path!r} can emit id "
            f"{max_id} >= gpt2 embedding table rows {cfg.vocab_size}; "
            "out-of-range ids would be silently clamped"
        )
    if not (0 <= cfg.eos_id < cfg.vocab_size and 0 <= cfg.pad_id < cfg.vocab_size):
        raise ValueError(
            f"tokenizer eos_id={cfg.eos_id}/pad_id={cfg.pad_id} outside "
            f"gpt2 vocab of {cfg.vocab_size}"
        )
    params = _load_weights("gpt2", svc_cfg, policy,
                           functools.partial(gpt_mod.init_params, cfg=cfg),
                           gpt2_state_to_pytree)

    # Optional shared system prompt: cached KV in the params pytree.
    p_len = _attach_prompt_prefix(
        params, tokenizer, svc_cfg,
        lambda p, ids: gpt_mod.compute_prefix_kv(
            p, cfg, ids, dtype=policy.compute_jnp
        ),
        cfg.max_position,
    )

    max_prompt = _decode_position_budget(svc_cfg, cfg.max_position, p_len, "gpt2")

    def encode_fn(p, input_ids, attention_mask):
        # Prompt passes through; the prefill forward happens in
        # init_state_fn — both live inside the same fused jit dispatch.
        return input_ids

    def init_state_fn(p, input_ids, enc_mask, max_len: int, sample=None):
        return gpt_mod.init_decode_state(
            p, cfg, input_ids, enc_mask, max_len, dtype=policy.compute_jnp,
            sample=sample,
        )

    def generate_chunk_fn(p, state, n_steps: int, sample: bool = False):
        return gpt_mod.generate_chunk(p, cfg, state, n_steps, sample)

    def paged_chunk_fn(p, state, table, n_steps: int, sample: bool = False):
        return gpt_mod.generate_chunk_paged(p, cfg, state, table, n_steps, sample)

    def empty_state_fn(p, batch: int, s_total: int, max_len: int):
        return gpt_mod.empty_decode_state(
            p, cfg, batch, s_total, max_len, dtype=policy.compute_jnp
        )

    def prefill_chunk_fn(p, state, ids, mask, start):
        return gpt_mod.prefill_chunk(
            p, cfg, state, ids, mask, start, dtype=policy.compute_jnp
        )

    def paged_prefill_chunk_fn(p, state, table_rows, ids, mask, starts):
        return gpt_mod.paged_prefill_chunk(
            p, cfg, state, table_rows, ids, mask, starts, dtype=policy.compute_jnp
        )

    from . import spec as spec_mod

    init_spec_fn = spec_mod.make_init_spec_fn(p_len)

    def spec_chunk_fn(p, spec_state, n_verify: int, spec_k: int,
                      sample: bool = False):
        return spec_mod.spec_chunk(
            p, spec_state, n_verify, spec_k, int(svc_cfg.spec_ngram),
            lambda pp, st, toks: gpt_mod.multi_step(pp, cfg, st, toks),
            cfg.eos_id, cfg.pad_id, sample,
        )

    return ModelBundle(
        name="gpt2",
        kind=KIND_SEQ2SEQ,
        cfg=cfg,
        params=params,
        policy=policy,
        tokenizer=tokenizer,
        labels=None,
        forward=None,
        encode_fn=encode_fn,
        init_state_fn=init_state_fn,
        generate_chunk_fn=generate_chunk_fn,
        max_prompt_len=max_prompt,
        # TP=<n>: decoder Megatron sharding (parallel/tp.py gpt spec).
        make_placement=_tp_placement(svc_cfg, cfg, "gpt"),
        supports_prefix=True,
        init_spec_fn=init_spec_fn,
        spec_chunk_fn=spec_chunk_fn,
        paged_chunk_fn=paged_chunk_fn,
        empty_state_fn=empty_state_fn,
        prefill_chunk_fn=prefill_chunk_fn,
        paged_prefill_chunk_fn=paged_prefill_chunk_fn,
    )


def _build_llama(svc_cfg, policy: DtypePolicy) -> ModelBundle:
    """Llama-family decoder (RoPE/GQA/SwiGLU — models/llama.py), served
    through the same seq2seq machinery as GPT-2 (fused prefill, chunked
    decode, continuous batching, sampling, TP).

    Default dims = TinyLlama-1.1B; ``LLAMA_CONFIG`` env takes a JSON
    object of LlamaConfig overrides (e.g. '{"num_layers": 16}') so one
    builder serves the whole dims family without code changes.
    """
    import json as _json
    import os as _os

    from ..convert import llama_state_to_pytree
    from . import llama as llama_mod

    # Llama input convention is the INVERSE of T5's: prompts start with
    # <s> (BOS) and must NOT end in </s> — a trailing EOS conditions the
    # model on end-of-document and derails generation.  SentencePiece
    # assets get the convention natively; other paths use the for_t5
    # fallback (byte fallback/eos layouts, bos-less).  A family whose
    # tokenizer has no BOS (OLMoE) says so in its config: ``add_bos``.
    overrides = {}
    env_cfg = _os.environ.get("LLAMA_CONFIG")
    if env_cfg:
        overrides = _json.loads(env_cfg)
    tok_path = svc_cfg.tokenizer_path
    if tok_path and tok_path.endswith((".model", ".tsv", ".vocab")):
        from .sentencepiece import load_sentencepiece

        tokenizer = _tokenizer(
            load_sentencepiece, tok_path, add_eos=False,
            add_bos=bool(overrides.get("add_bos", True)),
        )
    else:
        tokenizer = _tokenizer(build_tokenizer, tok_path, for_t5=True)
    # Model-side EOS/pad must be the TOKENIZER's ids (gpt2 precedent):
    # a mismatch would leave streams decoding the full budget while the
    # detokenizer silently truncates at its own eos.
    overrides.setdefault("eos_id", int(tokenizer.eos_id))
    overrides.setdefault("pad_id", int(tokenizer.pad_id))
    if getattr(svc_cfg, "quant_kv", None) == "int8":
        overrides["kv_quant"] = True
    # Pallas decode attention (ops/attention.decode_attention).
    # Policy from a pre-round record (removed in PR 22, to be
    # re-measured on the chip; llama-1.1B int8 weights, B=8): int8-KV through the fused kernel beats the
    # dense XLA path 1.32-1.58x across contexts 512-1792 — in-kernel
    # dequant is what flips round-4's 0.89-0.90x XLA kv-quant loss —
    # while the DENSE kernel variant loses slightly (0.86-0.96x).  So
    # the default follows the measurement: ON exactly when the int8 KV
    # cache is on.  USE_PALLAS_DECODE=1 forces it for dense too,
    # =0 disables.  TPU-gated like use_pallas_attention: the DEFAULT
    # follows the backend (the kernel has no CPU lowering), while an
    # explicit =1 that cannot be honoured fails the boot.
    env_pd = _os.environ.get("USE_PALLAS_DECODE", "").lower()
    want_pd = (
        env_pd in ("1", "true", "yes")
        or (env_pd not in ("0", "false", "no") and overrides.get("kv_quant"))
    )
    if want_pd:
        import math as _math

        from ..ops.attention import decode_kernel_fits

        # Worst-case cache width this deployment can reach.  The
        # per-request prefix cache never widens it (its admission guard
        # keeps p_len + suffix bucket <= the max seq bucket), but a
        # global PROMPT_PREFIX prepends its own tokens — estimate them
        # with the request tokenizer (upper bound: terminal specials
        # not yet stripped) so the VMEM-fit gate sees the real slab.
        probe = llama_mod.LlamaConfig(
            **{k: v for k, v in overrides.items() if k != "pallas_decode"}
        )
        p_est = 0
        if getattr(svc_cfg, "prompt_prefix", None):
            _, _pmask = tokenizer.encode(
                svc_cfg.prompt_prefix, probe.max_position
            )
            p_est = int(_pmask.sum())
        chunk = max(1, int(getattr(svc_cfg, "stream_chunk_tokens", 4)))
        t_est = p_est + max(svc_cfg.seq_buckets) + int(
            _math.ceil(svc_cfg.max_decode_len / chunk) * chunk
        )
        explicit = env_pd in ("1", "true", "yes")
        backend_ok = _pallas_backend_ok(svc_cfg)
        # A latent cache has no slab kernel to fit (it serves paged only:
        # refused below otherwise); its paged kernel's VMEM is the
        # autotuner's gate (ops/autotune.latent_vmem_bytes).
        fits = probe.mla or decode_kernel_fits(
            t_est, probe.num_kv_heads, probe.head_dim)
        if backend_ok and fits:
            overrides["pallas_decode"] = True
        elif explicit and not backend_ok:
            raise _pallas_decode_unavailable(
                "backend is not tpu and PALLAS_INTERPRET is off"
            )
        elif explicit:
            raise _pallas_decode_unavailable(
                f"the KV slab at T={t_est} exceeds "
                "DECODE_KERNEL_VMEM_BUDGET_MB"
            )
    overrides.update(_pallas_knobs(svc_cfg))
    cfg = llama_mod.LlamaConfig(**overrides)

    max_id = int(getattr(tokenizer, "max_token_id",
                         getattr(tokenizer, "vocab_size", 1) - 1))
    if max_id >= cfg.vocab_size:
        raise ValueError(
            f"tokenizer at {svc_cfg.tokenizer_path!r} can emit id {max_id} "
            f">= llama embedding table rows {cfg.vocab_size}"
        )
    if not (0 <= cfg.eos_id < cfg.vocab_size and 0 <= cfg.pad_id < cfg.vocab_size):
        raise ValueError(
            f"eos_id={cfg.eos_id}/pad_id={cfg.pad_id} outside llama vocab "
            f"of {cfg.vocab_size}"
        )
    variants = [
        name for name, on in (
            ("an expert FFN", cfg.num_experts), ("the q/k-norm", cfg.qk_norm),
            ("a layer pattern", cfg.layer_types or cfg.num_dense_layers),
            ("a head_dim of its own", cfg.q_dim != cfg.d_model),
            ("an attention gate", cfg.attn_gate),
            ("sandwich norms", cfg.sandwich_norm),
            ("latent attention", cfg.mla),
            ("a group limit on the router", cfg.n_group > 1),
            ("a chip's share of the experts", cfg.experts_held),
            ("mixer-or-FFN layers (layer_pattern)", cfg.layer_pattern),
            ("a clamped SwiGLU", cfg.swiglu_limit),
            ("a gated norm scale", cfg.norm_gate_weight),
            ("a tied head", cfg.tie_embeddings),
            ("differential attention", cfg.diff),
            ("LayerNorm block norms", cfg.norm == "layer"),
            ("attention biases", cfg.attn_bias),
        ) if on
    ]
    if variants:
        # The expert leaves ([E, d, w] kernels, a router, a shared expert),
        # the q/k-norm, the gate, the post-norms and a q projection wider
        # than d_model are in no TP param spec and no int8 scheme: refuse
        # what is not covered, never serve it wrong.
        what = variants[0]
        if int(getattr(svc_cfg, "tp", 0) or 0) > 1:
            raise ValueError(
                f"TP={svc_cfg.tp} is not supported for a llama config with "
                f"{what} (parallel/tp.llama_param_spec shards neither the "
                "stacked experts nor a norm over the whole q/k width); serve "
                "it on one chip"
            )
        if getattr(svc_cfg, "quantize", None):
            raise ValueError(
                f"QUANTIZE={svc_cfg.quantize} is not supported for a llama "
                f"config with {what} (models/quant.py has no per-expert "
                "scale for [E, d, w] kernels)"
            )
    if cfg.window:
        # The window binds in the prefill waves, the chunked paged prefill
        # and the paged decode step (kernel and gathered path:
        # models/llama.py).  Every other reader of the cache would attend
        # over ALL the keys and serve another model in silence: refuse it.
        _refuse_cache_readers(
            svc_cfg, f"window layers (layer_types / window={cfg.window})", {
                "PAGED_KV=0": "the contiguous slab's decode step and chunked "
                "prefill apply no window: set PAGED_KV=1",
                "SPEC_DECODE": "speculative verification (llama.multi_step) "
                "applies no window",
                "QUANT_KV": "the int8 pool pairs were never run under a window view",
                "PREFIX_CACHE": "a prefix hit's gathers and prefixed prefill "
                "apply no window",
                "PROMPT_PREFIX": "the prefix overlay's prefill applies no window",
            })
    if cfg.mla:
        # The cache is one latent row a token a layer (no heads axis, no V
        # pool), read by the prefill waves, the chunked paged prefill and
        # the paged decode step (kernel and gathered path: models/llama.py).
        # Every other reader of the cache expects K and V per KV head and
        # would cache or attend something else in silence: refuse it.
        # TP>1 (the latent would replicate) and QUANTIZE refuse above.
        _refuse_cache_readers(
            svc_cfg, "latent attention (attention='mla')", {
                "PAGED_KV=0": "the contiguous slab's chunked prefill and "
                "streaming loop read K and V per head: set PAGED_KV=1",
                "SPEC_DECODE": "speculative verification (llama.multi_step) "
                "reads K and V per head",
                "QUANT_KV": "the int8 pool pairs quantise per token-head; a "
                "latent has no heads",
                "PREFIX_CACHE": "a prefix hit's gathers and prefixed prefill "
                "read K and V per head",
                "PROMPT_PREFIX": "the prefix overlay's prefill reads K and V "
                "per head",
            })
    if cfg.recurrent_layers:
        # A recurrent layer's state (``llama.RECURRENT``: Mamba-2's, Gated
        # DeltaNet's, Mamba-1's) is a
        # fixed-size row a stream beside the cache — a K/V pool or, with
        # attention='mla', a latent pool: the refusals above hold as well —
        # carried by the prefill waves, the chunked paged prefill and the
        # paged decode step (models/llama.py, engine/streams.py's state
        # rows).  Every other reader or mover of a stream's state knows
        # keys and values only and would drop, share or skip the recurrence
        # in silence: refuse it.  TP>1 (no spec shards the recurrent heads)
        # and QUANTIZE refuse above.
        _refuse_cache_readers(
            svc_cfg, llama_mod.RECURRENT[cfg.layer_kind(
                cfg.recurrent_layers[0]).mixer].name, {
                "PAGED_KV=0": "the contiguous slab's chunked prefill cannot "
                "leave a prompt's last token out of the recurrent state: set "
                "PAGED_KV=1",
                "SPEC_DECODE": "speculative verification (llama.multi_step) "
                "cannot roll a recurrent state back over rejected tokens",
                "QUANT_KV": "the int8 pool pairs were never run beside a "
                "recurrent state",
                "PREFIX_CACHE": "a prefix hit shares blocks of keys; the "
                "recurrent state at the prefix's end is kept nowhere",
                "PROMPT_PREFIX": "the prefix overlay holds keys and values, "
                "no recurrent state at its end",
                "KV_HOST_BUDGET_MB": "the swap tiers move blocks of keys; a "
                "resumed stream's recurrent state would be missing (it is "
                "rebuilt by recompute instead: leave the tiers off)",
                "KV_DISK_BUDGET_MB": "the disk tier moves blocks of keys, no "
                "recurrent state",
            })
    if cfg.cross_from < cfg.num_layers or cfg.diff or cfg.window_ring:
        # A cross-decoder's layers own nothing: a 'cross' layer reads another
        # layer's pool after that layer's write of the same step, a Gated
        # Memory Unit the memory layer's scan output of the same positions;
        # a window layer's ring (window_ring) is a row a stream beside the
        # pool, not blocks; differential attention reads a cached token as
        # pairs [k1 | k2], [v1 | v2].  The prefill waves, the chunked paged
        # prefill and the paged decode step carry all of it (models/llama.py,
        # engine/streams.py's state rows).  Every other reader or mover of a
        # stream's keys knows one pool a layer, K and V a head, and would
        # share, move or attend something else in silence: refuse it.  TP>1
        # and QUANTIZE refuse above.
        _refuse_cache_readers(
            svc_cfg, "a cross-decoder, a window ring or differential attention "
            "(layer_types 'cross' / 'gmu', window_ring, attention='diff')", {
                "PAGED_KV=0": "the contiguous slab's chunked prefill and "
                "streaming loop know one cache entry a layer, each its own: "
                "set PAGED_KV=1",
                "SPEC_DECODE": "speculative verification (llama.multi_step) "
                "walks one cache entry a layer and carries no memory",
                "QUANT_KV": "the int8 pool pairs quantise per token-head; a "
                "differential pair is read two heads wide",
                "PREFIX_CACHE": "a prefix hit shares blocks of the one pool; "
                "the window rings and the state at the prefix's end are kept "
                "nowhere",
                "PROMPT_PREFIX": "the prefix overlay holds one entry a layer, "
                "no ring and no state",
                "KV_HOST_BUDGET_MB": "the swap tiers move blocks of the pool; "
                "a resumed stream's window rings would be missing",
                "KV_DISK_BUDGET_MB": "the disk tier moves blocks of the pool, "
                "no window ring",
            })
    if cfg.window_ring:
        bs = int(getattr(svc_cfg, "kv_block_size", 16))
        c = int(getattr(svc_cfg, "prefill_chunk", 0) or 0)
        # what a prompt window's view spans (llama.prefill_key_blocks)
        need = (-(-(c + cfg.window - 1) // bs) + 1) * bs
        if cfg.window_ring % bs or cfg.window_ring < need:
            raise ValueError(
                f"window_ring={cfg.window_ring} must be a multiple of "
                f"KV_BLOCK_SIZE={bs} and hold a prompt window's view: "
                f"PREFILL_CHUNK={c} + window={cfg.window} - 1 keys and a "
                f"block = {need}")
        if max(svc_cfg.seq_buckets) > cfg.window_ring:
            raise ValueError(
                f"SEQ_BUCKETS up to {max(svc_cfg.seq_buckets)} do not fit a "
                f"window ring of {cfg.window_ring} keys: a wave's prompt lands "
                "in the ring whole; longer prompts prefill in windows")
    if cfg.num_experts and not _pallas_backend_ok(svc_cfg):
        raise RuntimeError(
            "the expert FFN's grouped matmul (ops/moe.py) is a Pallas TPU "
            "kernel and the backend is not tpu: set PALLAS_INTERPRET=1 for "
            "the CPU interpret path"
        )
    # Each leaf is drawn in float32 and cast at once (llama.init_params):
    # the boot peak is the serving-dtype tree plus one leaf, not a whole
    # float32 tree.  A loaded checkpoint is cast here as before.
    params = _load_weights(
        "llama", svc_cfg, policy,
        functools.partial(llama_mod.init_params, cfg=cfg,
                          dtype=policy.param_jnp),
        llama_state_to_pytree)

    # Optional shared system prompt (cached KV).  The prefix carries
    # the BOS; request suffixes must then NOT get their own.
    p_len = _attach_prompt_prefix(
        params, tokenizer, svc_cfg,
        lambda p, ids: llama_mod.compute_prefix_kv(
            p, cfg, ids, dtype=policy.compute_jnp
        ),
        cfg.max_position,
    )
    if p_len and cfg.kv_quant:
        # The quantized cache stores every row as int8 + per-token
        # scale, the global prefix included: quantize it ONCE here
        # (startup), so init_decode_state writes prefix rows at int8
        # width and the fused Pallas decode kernel reads one uniform
        # int8 slab.  The prefill-side attention over the prefix
        # dequantizes these few rows per request (llama.forward_hidden).
        params["__prefix__"] = llama_mod.quantize_prefix_kv(
            params["__prefix__"]
        )
    if p_len and getattr(tokenizer, "add_bos", False):
        tokenizer.add_bos = False

    max_prompt = _decode_position_budget(svc_cfg, cfg.max_position, p_len, "llama")

    def encode_fn(p, input_ids, attention_mask):
        return input_ids

    def init_state_fn(p, input_ids, enc_mask, max_len: int, sample=None):
        return llama_mod.init_decode_state(
            p, cfg, input_ids, enc_mask, max_len, dtype=policy.compute_jnp,
            sample=sample,
        )

    def generate_chunk_fn(p, state, n_steps: int, sample: bool = False):
        return llama_mod.generate_chunk(p, cfg, state, n_steps, sample)

    def paged_chunk_fn(p, state, table, n_steps: int, sample: bool = False):
        return llama_mod.generate_chunk_paged(
            p, cfg, state, table, n_steps, sample
        )

    def empty_state_fn(p, batch: int, s_total: int, max_len: int):
        return llama_mod.empty_decode_state(
            p, cfg, batch, s_total, max_len, dtype=policy.compute_jnp
        )

    def prefill_chunk_fn(p, state, ids, mask, start):
        return llama_mod.prefill_chunk(
            p, cfg, state, ids, mask, start, dtype=policy.compute_jnp
        )

    # A chip's share of the experts: a window's expert block runs the rung
    # of ops/moe.row_rungs its held assignments need, so the dispatch also
    # returns what says which — (counts [L, E], the call's tokens) — for
    # the loop's next fetch (moe_rows_total).  Not the LAST layer's: a
    # window keeps no hidden state, so XLA removes that layer's FFN (and
    # the attention output under it) from the executable, and asking for
    # its counts would bring both back (+16 ms a GigaChat dispatch).
    # Likewise a tree whose rows are wide enough for the block's DMA
    # kernels (ops/moe.row_kernels_fit): the loop counts the held rows of
    # the calls that took them (moe_rows_fused_total).
    from ..ops.moe import rows_fit_kernels

    counted = sum(li != cfg.num_layers - 1 for li in cfg.expert_layers)
    share = bool(counted) and (
        cfg.held != cfg.num_experts
        or rows_fit_kernels(cfg.moe_latent or cfg.d_model, policy.compute_jnp))

    def paged_prefill_chunk_fn(p, state, table_rows, ids, mask, starts,
                               ssm_rows=None):
        tally = [] if share else None
        state = llama_mod.paged_prefill_chunk(
            p, cfg, state, table_rows, ids, mask, starts,
            dtype=policy.compute_jnp, ssm_rows=ssm_rows, tally=tally,
        )
        if share:
            import jax.numpy as jnp

            return state, (jnp.stack(tally[:counted]), jnp.int32(ids.size))
        return state

    from . import spec as spec_mod

    init_spec_fn = spec_mod.make_init_spec_fn(p_len)

    def spec_chunk_fn(p, spec_state, n_verify: int, spec_k: int,
                      sample: bool = False):
        return spec_mod.spec_chunk(
            p, spec_state, n_verify, spec_k, int(svc_cfg.spec_ngram),
            lambda pp, st, toks: llama_mod.multi_step(pp, cfg, st, toks),
            cfg.eos_id, cfg.pad_id, sample,
        )

    def logits_fn(p, input_ids, attention_mask):
        return llama_mod.lm_logits(
            p, cfg, input_ids, attention_mask, dtype=policy.compute_jnp
        )

    return ModelBundle(
        name="llama",
        kind=KIND_SEQ2SEQ,
        cfg=cfg,
        params=params,
        policy=policy,
        tokenizer=tokenizer,
        labels=None,
        forward=None,
        encode_fn=encode_fn,
        init_state_fn=init_state_fn,
        generate_chunk_fn=generate_chunk_fn,
        max_prompt_len=max_prompt,
        make_placement=_tp_placement(svc_cfg, cfg, "llama"),
        supports_prefix=True,
        init_spec_fn=init_spec_fn,
        spec_chunk_fn=spec_chunk_fn,
        paged_chunk_fn=paged_chunk_fn,
        empty_state_fn=empty_state_fn,
        prefill_chunk_fn=prefill_chunk_fn,
        paged_prefill_chunk_fn=paged_prefill_chunk_fn,
        logits_fn=logits_fn,
    )


MODEL_REGISTRY: dict[str, Callable] = {
    "resnet50": _build_resnet,
    "bert-base": _build_bert,
    "bert-long": _build_bert_long,
    "t5-small": _build_t5,
    "gpt2": _build_gpt,
    "llama": _build_llama,
}
MODEL_REGISTRY["tinyllama"] = _build_llama
# Aliases for HF-style names the reference's configs use.
MODEL_REGISTRY["resnet-50"] = _build_resnet
MODEL_REGISTRY["bert-base-uncased"] = _build_bert
MODEL_REGISTRY["t5small"] = _build_t5


def register_model(name: str, builder: Callable) -> None:
    """The template's extension point: plug YOUR model into the stack.

    The reference repo is a *template* — its README tells users to
    implement their model behind ``ModelWrapper`` hooks and get the
    HTTP service, batching and deployment for free (SURVEY.md §1–2).
    Same contract here: register ``builder(svc_cfg, policy) ->
    ModelBundle`` under a name, set ``MODEL_NAME=<name>``, and the
    engine/scheduler/API serve it with bucketed jit, dynamic batching
    and replica sharding unchanged.  See
    ``docs/custom_models.md`` for a worked example.
    """
    if not callable(builder):
        raise TypeError("builder must be callable(svc_cfg, policy) -> ModelBundle")
    if name in MODEL_REGISTRY:
        log.warning("register_model: overriding existing model %r", name)
    MODEL_REGISTRY[name] = builder


def build_model(svc_cfg, policy: DtypePolicy | None = None) -> ModelBundle:
    if policy is None:
        from ..runtime.device import default_policy

        policy = default_policy(svc_cfg.device)
    try:
        builder = MODEL_REGISTRY[svc_cfg.model_name]
    except KeyError:
        raise ValueError(
            f"unknown model {svc_cfg.model_name!r}; available: {sorted(MODEL_REGISTRY)}"
        ) from None
    bundle = builder(svc_cfg, policy)
    # TP must never be silently ignored: a model deployed BECAUSE
    # sharding makes it fit would otherwise OOM per-device with no
    # warning.  (bert-long composes SP, not TP, by design.)
    if int(getattr(svc_cfg, "tp", 0) or 0) > 1 and bundle.make_placement is None:
        raise ValueError(
            f"TP={svc_cfg.tp} is not supported for {svc_cfg.model_name!r} "
            "(tensor-parallel serving covers bert-base, gpt2 and llama; "
            "bert-long scales via SP/REPLICAS instead)"
        )
    # A configured PROMPT_PREFIX that a model silently drops would serve
    # un-prefixed generations with no warning — reject instead.
    if getattr(svc_cfg, "prompt_prefix", None) and not bundle.supports_prefix:
        raise ValueError(
            f"PROMPT_PREFIX is not supported for {svc_cfg.model_name!r} "
            "(cached-prefix serving covers the decoder families: gpt2, llama)"
        )
    # Same rule for SPEC_DECODE: an operator who turned it on must not
    # silently serve without it (zero speedup, no metric, no error).
    if getattr(svc_cfg, "spec_decode", None) and bundle.spec_chunk_fn is None:
        raise ValueError(
            f"SPEC_DECODE is not supported for {svc_cfg.model_name!r} "
            "(speculative decoding covers the generative families: "
            "gpt2, llama, t5-small)"
        )
    if getattr(svc_cfg, "quant_kv", None):
        # QUANT_KV now COMPOSES with both prefix knobs (round-6): prefix
        # KV is captured/attached as int8+per-row-scale entries the
        # quantized cache absorbs directly (llama._quant_prefix_entry),
        # so the only retained restriction is the family one.
        if bundle.name != "llama":
            raise ValueError(
                f"QUANT_KV is not supported for {svc_cfg.model_name!r} "
                "(int8 KV cache covers the llama family)"
            )
    if getattr(svc_cfg, "spec_continuous", False):
        # PREFIX_CACHE no longer excluded (round-6): hit-group batched
        # wave states recast through init_spec_fn at slot-insert time
        # (engine/streams.py), so prefix-hit streams join the spec slot
        # batch like any other admission.
        if not getattr(svc_cfg, "spec_decode", None):
            raise ValueError(
                "SPEC_CONTINUOUS requires SPEC_DECODE=ngram (it is the "
                "continuous-loop extension of speculative decoding)"
            )
    if getattr(svc_cfg, "paged_kv", False):
        # PAGED_KV v1 scope (docs/kv-paging.md): block-paged decode in
        # the continuous loop, decoder-only families.  Every unsupported
        # combination rejects loudly — a silently-contiguous deployment
        # would report paged occupancy wins it isn't getting.
        if bundle.paged_chunk_fn is None:
            raise ValueError(
                f"PAGED_KV is not supported for {svc_cfg.model_name!r} "
                "(block-paged KV covers the decoder families: gpt2, llama)"
            )
        if getattr(svc_cfg, "prompt_prefix", None):
            raise ValueError(
                "PAGED_KV and PROMPT_PREFIX are mutually exclusive: the "
                "global prefix overlay predates the block pool — use "
                "PREFIX_CACHE=1, whose hits SHARE prompt blocks by "
                "refcount"
            )
        if getattr(svc_cfg, "spec_continuous", False):
            raise ValueError(
                "PAGED_KV does not yet compose with SPEC_CONTINUOUS "
                "(speculative verify windows write multi-token spans "
                "through the table; planned follow-up)"
            )
        # Bucket alignment is no longer a rejection: ServiceConfig
        # block-aligns the seq bucket grid at parse time (rounding up,
        # deduped — utils/config._align_paged_seq_buckets).  Guard the
        # invariant here for duck-typed configs that bypassed pydantic.
        bs = int(getattr(svc_cfg, "kv_block_size", 16))
        bad = [b for b in svc_cfg.seq_buckets if b % bs]
        if bad:
            raise ValueError(
                f"KV_BLOCK_SIZE={bs} must divide every seq bucket; "
                f"ServiceConfig aligns the grid at parse time, but this "
                f"config bypassed it (offending buckets: {bad})"
            )
        if int(getattr(svc_cfg, "replicas", 0) or 0) > 1:
            raise ValueError(
                "PAGED_KV requires REPLICAS=1: the block pool has no "
                "batch axis to shard over the replica mesh"
            )
    if int(getattr(svc_cfg, "prefill_chunk", 0) or 0) > 0:
        # Chunked prefill (docs/chunked-prefill.md) changes the loop's
        # dispatch unit; every unsupported combination rejects loudly —
        # a silently-monolithic deployment would report interference
        # wins it isn't getting.
        if bundle.prefill_chunk_fn is None:
            raise ValueError(
                f"PREFILL_CHUNK is not supported for {svc_cfg.model_name!r} "
                "(chunked prefill covers the decoder families gpt2/llama; "
                "encoder-decoders like t5 prefill the DECODER from a start "
                "token — the encoder pass has no incremental KV to chunk)"
            )
        if getattr(svc_cfg, "prompt_prefix", None):
            raise ValueError(
                "PREFILL_CHUNK and PROMPT_PREFIX are mutually exclusive: "
                "the global prefix overlay seeds positions 0..P inside "
                "init_decode_state, which chunked prefill bypasses — use "
                "PREFIX_CACHE=1, whose hits suffix-prefill in chunks"
            )
        if getattr(svc_cfg, "spec_continuous", False):
            raise ValueError(
                "PREFILL_CHUNK does not compose with SPEC_CONTINUOUS "
                "(the spec slot insert rebuilds the drafting history from "
                "a monolithic collated prompt; planned follow-up)"
            )
        if getattr(svc_cfg, "paged_kv", False):
            bs = int(getattr(svc_cfg, "kv_block_size", 16))
            if int(svc_cfg.prefill_chunk) % bs:
                raise ValueError(
                    f"PREFILL_CHUNK={svc_cfg.prefill_chunk} must be a "
                    f"multiple of KV_BLOCK_SIZE={bs} so every window "
                    "boundary is block-aligned (per-chunk block growth "
                    "stays exact)"
                )
    if getattr(svc_cfg, "prefix_cache", False):
        if not bundle.supports_prefix:
            raise ValueError(
                f"PREFIX_CACHE is not supported for {svc_cfg.model_name!r} "
                "(per-request prefix caching covers the decoder "
                "families: gpt2, llama)"
            )
        if getattr(svc_cfg, "prompt_prefix", None):
            raise ValueError(
                "PREFIX_CACHE and PROMPT_PREFIX are mutually exclusive: "
                "the global prefix occupies positions 0..P that "
                "per-request prefixes need (the cache generalizes the "
                "global knob — drop PROMPT_PREFIX)"
            )
    return bundle
