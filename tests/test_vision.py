"""Multimodal dual encoder (models/vision.py) + MultimodalEmbedder xpack.

Beyond-reference capability (BASELINE.md multimodal RAG config); the
reference's embedders are text-only (xpacks/llm/embedders.py:85-401).
"""

import numpy as np
import pytest

from pathway_tpu.models.vision import (
    MultimodalEncoder,
    _resize_bilinear,
    patchify,
    vision_config_for,
)

ENC = MultimodalEncoder("pw-tiny-siglip")


def test_unknown_model_raises():
    with pytest.raises(ValueError, match="unknown multimodal model"):
        vision_config_for("siglip-maxi")


def test_patchify_shapes_and_content():
    cfg, _ = vision_config_for("pw-tiny-siglip")
    imgs = np.arange(2 * 32 * 32 * 3, dtype=np.float32).reshape(2, 32, 32, 3)
    import jax.numpy as jnp

    patches = np.asarray(patchify(jnp.asarray(imgs), cfg.patch))
    assert patches.shape == (2, cfg.n_patches, cfg.patch * cfg.patch * 3)
    # first patch of first image == top-left 8x8 block, row-major
    expect = imgs[0, :8, :8, :].reshape(-1)
    np.testing.assert_array_equal(patches[0, 0], expect)


def test_image_embeddings_normalized_and_deterministic():
    rng = np.random.default_rng(0)
    imgs = rng.random((3, 32, 32, 3)).astype(np.float32)
    a = ENC.embed_images(imgs)
    b = ENC.embed_images(imgs)
    assert a.shape == (3, ENC.dimensions)
    np.testing.assert_allclose(np.linalg.norm(a, axis=1), 1.0, atol=1e-5)
    np.testing.assert_array_equal(a, b)


def test_text_embeddings_share_space():
    te = ENC.embed_texts(["a photo of a cat", "finance report"])
    assert te.shape == (2, ENC.dimensions)
    np.testing.assert_allclose(np.linalg.norm(te, axis=1), 1.0, atol=1e-5)


def test_batch_padding_invariance():
    """A row's embedding doesn't depend on batch padding/composition."""
    rng = np.random.default_rng(1)
    imgs = rng.random((5, 32, 32, 3)).astype(np.float32)
    all_at_once = ENC.embed_images(imgs)
    solo = ENC.embed_images(imgs[2:3])
    np.testing.assert_allclose(all_at_once[2], solo[0], atol=1e-5)


def test_uint8_and_resize_paths():
    rng = np.random.default_rng(2)
    img8 = rng.integers(0, 256, size=(1, 48, 40, 3)).astype(np.uint8)
    out = ENC.embed_images(img8)
    assert out.shape == (1, ENC.dimensions)
    assert np.isfinite(out).all()


def test_resize_bilinear_identity_and_interp():
    x = np.random.default_rng(3).random((1, 16, 16, 3)).astype(np.float32)
    same = _resize_bilinear(x, 16)
    np.testing.assert_allclose(same, x, atol=1e-6)
    up = _resize_bilinear(x, 32)
    assert up.shape == (1, 32, 32, 3)
    assert up.min() >= x.min() - 1e-6 and up.max() <= x.max() + 1e-6


def test_pairwise_scores_shape():
    rng = np.random.default_rng(4)
    imgs = rng.random((2, 32, 32, 3)).astype(np.float32)
    scores = ENC.score(imgs, ["one", "two", "three"])
    assert scores.shape == (2, 3)
    assert np.isfinite(scores).all()


def test_multimodal_embedder_mixed_pipeline():
    """Text rows and image rows (npy bytes) embed through one UDF into the
    same dimensionality."""
    import io

    import pathway_tpu as pw
    from pathway_tpu.xpacks.llm.embedders import MultimodalEmbedder

    emb = MultimodalEmbedder(model="pw-tiny-siglip")
    rng = np.random.default_rng(5)
    buf = io.BytesIO()
    np.save(buf, rng.integers(0, 256, size=(20, 20, 3)).astype(np.uint8))
    img_bytes = buf.getvalue()

    rows = [{"data": "a text document"}, {"data": img_bytes}]
    t = pw.debug.table_from_rows(
        schema=pw.schema_from_types(data=pw.internals.dtype.ANY),
        rows=[(r["data"],) for r in rows],
    )
    res = t.select(v=emb(pw.this.data))
    df = pw.debug.table_to_pandas(res)
    vecs = [np.asarray(v) for v in df["v"].tolist()]
    assert len(vecs) == 2
    assert all(v.shape == (emb.get_embedding_dimension(),) for v in vecs)
    assert emb.get_embedding_dimension() == 32


def test_decode_image_variants():
    from pathway_tpu.xpacks.llm.embedders import _decode_image

    assert _decode_image("just text", 32) is None
    assert _decode_image(None, 32) is None
    assert _decode_image(b"not an image", 32) is None
    gray = np.random.default_rng(6).random((10, 10)).astype(np.float32)
    out = _decode_image(gray, 32)
    assert out.shape == (32, 32, 3)
    rgba = np.random.default_rng(7).random((10, 10, 4)).astype(np.float32)
    assert _decode_image(rgba, 32).shape == (32, 32, 3)


def test_decode_image_channel_layouts():
    from pathway_tpu.xpacks.llm.embedders import _decode_image

    rng = np.random.default_rng(8)
    hw1 = rng.random((10, 10, 1)).astype(np.float32)
    assert _decode_image(hw1, 32).shape == (32, 32, 3)
    hw2 = rng.random((10, 10, 2)).astype(np.float32)
    assert _decode_image(hw2, 32).shape == (32, 32, 3)
    chw = rng.random((3, 20, 20)).astype(np.float32)
    out = _decode_image(chw, 32)
    assert out.shape == (32, 32, 3)
    # channel content survives the CHW->HWC transpose (not a width slice)
    np.testing.assert_allclose(
        _decode_image(chw.transpose(1, 2, 0), 32), out, atol=1e-6
    )


def test_long_prompt_tail_reaches_decoder():
    """Chat prompts longer than the cache keep their tail end-to-end (the
    tokenizer must not head-truncate at the cache limit first)."""
    from pathway_tpu.models.decoder import DecoderLM

    lm = DecoderLM("pw-tiny-decoder", max_cache=64, eos_id=None)
    long_prompt = " ".join(f"word{i}" for i in range(300))
    ids_full = lm._encode_prompt(long_prompt)
    assert len(ids_full) > 64  # tokenized at the model limit, not cache
    from pathway_tpu.serving.generation import GenerationScheduler
    from tests.decoder_oracle import generate_ids

    sched = GenerationScheduler(lm)
    try:
        out = sched.generate(long_prompt, max_new_tokens=4)
    finally:
        sched.shutdown()
    # equals generating from the kept tail explicitly
    tail = ids_full[-(64 - 4):]
    expect = generate_ids(lm, [tail], max_new_tokens=4)[0]
    assert out == lm.tokenizer.decode(expect)
