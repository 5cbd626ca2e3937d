"""Host-side pre/post-processing as pure functions (numpy in/out).

Capability parity: the reference's ``ModelWrapper`` owns PIL decode +
ImageNet normalization for ResNet and label mapping for outputs
(SURVEY.md §2). Kept lean — this box serves from 1 vCPU shared with the
event loop (SURVEY.md §7.4.3), so decode/resize happen in a thread-pool
offload (see ``scheduler``), and everything here is allocation-light.
"""

from __future__ import annotations

import io

import numpy as np

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def decode_image_u8(data: bytes, image_size: int = 224) -> np.ndarray:
    """JPEG/PNG bytes → [H, W, 3] uint8 (resize-shortest + center crop).

    Normalization deliberately does NOT happen here: uint8 crosses the
    host→device boundary at 1/4 the bytes of f32, and the mean/std
    affine runs on-device inside the jitted forward (fused into the
    first conv by XLA) — a 4× cut of the bytes an image request moves
    to the device.
    """
    from PIL import Image

    img = Image.open(io.BytesIO(data)).convert("RGB")
    w, h = img.size
    short = int(round(image_size * 256 / 224))
    if w < h:
        nw, nh = short, max(1, int(round(h * short / w)))
    else:
        nw, nh = max(1, int(round(w * short / h))), short
    img = img.resize((nw, nh), Image.BILINEAR)
    left = (nw - image_size) // 2
    top = (nh - image_size) // 2
    img = img.crop((left, top, left + image_size, top + image_size))
    return np.asarray(img, np.uint8)


def normalize_imagenet(x):
    """Device-side ImageNet normalization: uint8 [.., 3] → f32.

    Lives next to the host decode so the two halves of the reference's
    preprocessing (SURVEY.md §2 ModelWrapper) stay in one place.
    """
    import jax.numpy as jnp

    mean = jnp.asarray(IMAGENET_MEAN)
    std = jnp.asarray(IMAGENET_STD)
    return (x.astype(jnp.float32) / 255.0 - mean) / std


def softmax_np(logits: np.ndarray) -> np.ndarray:
    z = logits - logits.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


def topk_np(logits: np.ndarray, k: int = 5) -> tuple[np.ndarray, np.ndarray]:
    """Per-row top-k (indices, probabilities), sorted descending."""
    probs = softmax_np(logits.astype(np.float32))
    idx = np.argpartition(-probs, kth=min(k, probs.shape[-1] - 1), axis=-1)[..., :k]
    vals = np.take_along_axis(probs, idx, axis=-1)
    order = np.argsort(-vals, axis=-1)
    return np.take_along_axis(idx, order, axis=-1), np.take_along_axis(vals, order, axis=-1)


def load_labels(path: str | None) -> list[str] | None:
    """Optional label file: one class name per line (LABELS_PATH)."""
    if not path:
        return None
    with open(path, encoding="utf-8") as f:
        return [line.rstrip("\n") for line in f]
