"""Name/layout maps from HuggingFace state dicts to our param pytrees.

Input is always ``{name: numpy.ndarray}`` (call ``.numpy()`` on torch
tensors before passing, or load a safetensors file directly), output is
a nested-dict pytree matching ``models/{resnet,bert,t5}.init_params``.

Layout conversions performed here (SURVEY.md §7.4.5 — the classic
torch↔JAX pitfalls):
- conv kernels OIHW → HWIO (transpose 2,3,1,0)
- linear weights [out, in] → [in, out] (transpose)
- embeddings and norm vectors pass through unchanged
"""

from __future__ import annotations

import numpy as np

Array = np.ndarray
State = dict[str, Array]


def _conv(w: Array) -> Array:
    return np.ascontiguousarray(np.transpose(w, (2, 3, 1, 0)))


def _lin(w: Array) -> Array:
    return np.ascontiguousarray(np.transpose(w, (1, 0)))


def _bn(state: State, prefix: str) -> dict:
    return {
        "scale": state[f"{prefix}.weight"],
        "bias": state[f"{prefix}.bias"],
        "mean": state[f"{prefix}.running_mean"],
        "var": state[f"{prefix}.running_var"],
    }


# ---------------------------------------------------------------------------
# ResNet (HF ResNetForImageClassification)


def resnet_state_to_pytree(state: State, depths=(3, 4, 6, 3)) -> dict:
    p: dict = {
        "embedder": {
            "conv": {"kernel": _conv(state["resnet.embedder.embedder.convolution.weight"])},
            "bn": _bn(state, "resnet.embedder.embedder.normalization"),
        }
    }
    stages = []
    for si, depth in enumerate(depths):
        blocks = []
        for bi in range(depth):
            base = f"resnet.encoder.stages.{si}.layers.{bi}"
            block: dict = {}
            if f"{base}.shortcut.convolution.weight" in state:
                block["shortcut"] = {
                    "conv": {"kernel": _conv(state[f"{base}.shortcut.convolution.weight"])},
                    "bn": _bn(state, f"{base}.shortcut.normalization"),
                }
            for li, (cname, bname) in enumerate(
                [("conv1", "bn1"), ("conv2", "bn2"), ("conv3", "bn3")]
            ):
                block[cname] = {"kernel": _conv(state[f"{base}.layer.{li}.convolution.weight"])}
                block[bname] = _bn(state, f"{base}.layer.{li}.normalization")
            blocks.append(block)
        stages.append(blocks)
    p["stages"] = stages
    p["classifier"] = {
        "kernel": _lin(state["classifier.1.weight"]),
        "bias": state["classifier.1.bias"],
    }
    return p


# ---------------------------------------------------------------------------
# BERT (HF BertForSequenceClassification)


def bert_state_to_pytree(state: State, n_layers: int = 12) -> dict:
    def ln(prefix: str) -> dict:
        return {"scale": state[f"{prefix}.weight"], "bias": state[f"{prefix}.bias"]}

    def lin(prefix: str) -> dict:
        return {"kernel": _lin(state[f"{prefix}.weight"]), "bias": state[f"{prefix}.bias"]}

    p: dict = {
        "embeddings": {
            "word": {"embedding": state["bert.embeddings.word_embeddings.weight"]},
            "position": {"embedding": state["bert.embeddings.position_embeddings.weight"]},
            "token_type": {"embedding": state["bert.embeddings.token_type_embeddings.weight"]},
            "ln": ln("bert.embeddings.LayerNorm"),
        },
        "layers": [],
    }
    for i in range(n_layers):
        base = f"bert.encoder.layer.{i}"
        p["layers"].append(
            {
                "attn": {
                    "q": lin(f"{base}.attention.self.query"),
                    "k": lin(f"{base}.attention.self.key"),
                    "v": lin(f"{base}.attention.self.value"),
                    "out": lin(f"{base}.attention.output.dense"),
                    "ln": ln(f"{base}.attention.output.LayerNorm"),
                },
                "mlp": {
                    "up": lin(f"{base}.intermediate.dense"),
                    "down": lin(f"{base}.output.dense"),
                    "ln": ln(f"{base}.output.LayerNorm"),
                },
            }
        )
    if "bert.pooler.dense.weight" in state:
        p["pooler"] = lin("bert.pooler.dense")
    if "classifier.weight" in state:
        p["classifier"] = lin("classifier")
    return p


# ---------------------------------------------------------------------------
# T5 (HF T5ForConditionalGeneration)


def t5_state_to_pytree(state: State, n_layers: int = 6) -> dict:
    def rms(prefix: str) -> dict:
        return {"scale": state[f"{prefix}.weight"]}

    def lin(prefix: str) -> dict:
        # T5 linears have no bias.
        return {"kernel": _lin(state[f"{prefix}.weight"])}

    def attn(base: str, cross: bool = False) -> dict:
        d = {
            "q": lin(f"{base}.q"),
            "k": lin(f"{base}.k"),
            "v": lin(f"{base}.v"),
            "out": lin(f"{base}.o"),
        }
        rp = f"{base}.relative_attention_bias.weight"
        if rp in state:
            d["rel_bias"] = {"embedding": state[rp]}
        return d

    p: dict = {
        "shared": {"embedding": state["shared.weight"]},
        "encoder": {"layers": [], "final_ln": rms("encoder.final_layer_norm")},
        "decoder": {"layers": [], "final_ln": rms("decoder.final_layer_norm")},
    }
    for i in range(n_layers):
        b = f"encoder.block.{i}.layer"
        p["encoder"]["layers"].append(
            {
                "attn": attn(f"{b}.0.SelfAttention"),
                "attn_ln": rms(f"{b}.0.layer_norm"),
                "mlp": {
                    "wi": lin(f"{b}.1.DenseReluDense.wi"),
                    "wo": lin(f"{b}.1.DenseReluDense.wo"),
                },
                "mlp_ln": rms(f"{b}.1.layer_norm"),
            }
        )
    for i in range(n_layers):
        b = f"decoder.block.{i}.layer"
        p["decoder"]["layers"].append(
            {
                "self_attn": attn(f"{b}.0.SelfAttention"),
                "self_attn_ln": rms(f"{b}.0.layer_norm"),
                "cross_attn": attn(f"{b}.1.EncDecAttention", cross=True),
                "cross_attn_ln": rms(f"{b}.1.layer_norm"),
                "mlp": {
                    "wi": lin(f"{b}.2.DenseReluDense.wi"),
                    "wo": lin(f"{b}.2.DenseReluDense.wo"),
                },
                "mlp_ln": rms(f"{b}.2.layer_norm"),
            }
        )
    if "lm_head.weight" in state:
        p["lm_head"] = {"kernel": _lin(state["lm_head.weight"])}
    return p


# ---------------------------------------------------------------------------
# GPT-2 (HF GPT2LMHeadModel)


def gpt2_state_to_pytree(state: State, n_layers: int = 12) -> dict:
    """HF ``transformer.*`` names → ``models/gpt.init_params`` layout.

    GPT-2's linear layers are HF ``Conv1D`` modules whose weights are
    already stored [in, out] — the one transformer family where NO
    transpose is needed (unlike nn.Linear's [out, in]).
    """

    def ln(prefix: str) -> dict:
        return {"scale": state[f"{prefix}.weight"], "bias": state[f"{prefix}.bias"]}

    def conv1d(prefix: str) -> dict:
        return {"kernel": state[f"{prefix}.weight"], "bias": state[f"{prefix}.bias"]}

    p: dict = {
        "wte": {"embedding": state["transformer.wte.weight"]},
        "wpe": {"embedding": state["transformer.wpe.weight"]},
        "layers": [],
        "final_ln": ln("transformer.ln_f"),
    }
    for i in range(n_layers):
        b = f"transformer.h.{i}"
        p["layers"].append(
            {
                "ln1": ln(f"{b}.ln_1"),
                "attn": {
                    "qkv": conv1d(f"{b}.attn.c_attn"),
                    "out": conv1d(f"{b}.attn.c_proj"),
                },
                "ln2": ln(f"{b}.ln_2"),
                "mlp": {
                    "up": conv1d(f"{b}.mlp.c_fc"),
                    "down": conv1d(f"{b}.mlp.c_proj"),
                },
            }
        )
    return p


def llama_state_to_pytree(state: State, n_layers: int | None = None) -> dict:
    """HF Llama-family names → ``models/llama.init_params`` layout.

    All projections are ``nn.Linear`` ([out, in] → transpose); norms are
    RMSNorm weight vectors; ``lm_head.weight`` [V, D] transposes to the
    untied [D, V] kernel.  Tied-embedding checkpoints (no ``lm_head``
    key) fall back to the embedding table transposed.

    OLMoE-style layers are recognised by their names: ``mlp.gate.weight``
    [E, D] is the router (→ [D, E]), ``mlp.experts.N.{gate,up,down}_proj``
    stack over N into [E, D, W] / [E, W, D], and
    ``self_attn.{q,k}_norm.weight`` are the q/k-norm scales.
    """
    if n_layers is None:
        n_layers = 1 + max(
            int(k.split(".")[2])
            for k in state
            if k.startswith("model.layers.")
        )

    def lin(prefix: str) -> dict:
        return {"kernel": _lin(state[f"{prefix}.weight"])}

    embed_w = state["model.embed_tokens.weight"]
    head = state.get("lm_head.weight", embed_w)
    p: dict = {
        "embed": {"embedding": embed_w},
        "layers": [],
        "final_ln": {"scale": state["model.norm.weight"]},
        "lm_head": {"kernel": _lin(head)},
    }
    def experts(b: str, name: str) -> dict:
        n = state[f"{b}.mlp.gate.weight"].shape[0]  # the router is [E, D]
        return {"kernel": np.stack(
            [_lin(state[f"{b}.mlp.experts.{e}.{name}.weight"]) for e in range(n)]
        )}

    for i in range(n_layers):
        b = f"model.layers.{i}"
        attn = {n: lin(f"{b}.self_attn.{n}_proj") for n in "qkvo"}
        for n in ("q_norm", "k_norm"):
            if f"{b}.self_attn.{n}.weight" in state:
                attn[n] = {"scale": state[f"{b}.self_attn.{n}.weight"]}
        if f"{b}.mlp.gate.weight" in state:
            mlp = {"router": lin(f"{b}.mlp.gate")}
            mlp.update({n: experts(b, f"{n}_proj") for n in ("gate", "up", "down")})
        else:
            mlp = {n: lin(f"{b}.mlp.{n}_proj") for n in ("gate", "up", "down")}
        p["layers"].append(
            {
                "attn_ln": {"scale": state[f"{b}.input_layernorm.weight"]},
                "attn": attn,
                "mlp_ln": {"scale": state[f"{b}.post_attention_layernorm.weight"]},
                "mlp": mlp,
            }
        )
    return p
