"""Unified benchmark runner (reference: benchmark/fluid/fluid_benchmark.py).

Runs any model from the zoo for N timed iterations and reports throughput:

  python benchmarks/fluid_benchmark.py --model resnet50 --batch_size 128
  python benchmarks/fluid_benchmark.py --model transformer --batch_size 64
  models: mnist vgg16 resnet50 se_resnext stacked_dynamic_lstm transformer
          word2vec deepfm ocr_crnn_ctc ssd recommender label_semantic_roles

On TPU, image/transformer models run bf16-on-MXU shapes; on CPU shapes are
shrunk so the run stays quick.  Synthetic data by default (the reference's
--use_fake_data path) so results measure compute, not input IO;
``--real_data`` feeds image models from the real input pipeline
(jpeg corpus -> pre-decoded uint8 recordio -> crop/flip workers, see
reader/image_pipeline.py — the reference's non-fake-data mode).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _on_tpu():
    import jax

    return jax.default_backend() == "tpu"


def _synth(model_name, model, batch, rng):
    """Synthetic feed dict + unit-count per step for throughput."""
    from paddle_tpu.lod import LoDArray

    if model_name in ("mnist",):
        return {"pixel": rng.randn(batch, 1, 28, 28).astype("float32"),
                "label": rng.randint(0, 10, size=(batch, 1)).astype("int64")}, batch, "images/sec"
    if model_name in ("vgg16", "resnet50", "se_resnext"):
        shape = model.get("image_shape", (3, 224, 224))
        return {"data": rng.randn(batch, *shape).astype("float32"),
                "label": rng.randint(0, 1000, size=(batch, 1)).astype("int64")}, batch, "images/sec"
    if model_name == "stacked_dynamic_lstm":
        T = 128
        lens = np.full((batch,), T, np.int32)
        return {"words": LoDArray(rng.randint(0, 5000, size=(batch, T)).astype("int64"), lens),
                "label": rng.randint(0, 2, size=(batch, 1)).astype("int64")}, batch * T, "tokens/sec"
    if model_name == "transformer":
        L = model["seq_len"]
        ids = rng.randint(1, 30000, size=(batch, L)).astype("int64")
        return {"src_word": ids, "trg_word": ids, "lbl_word": ids}, 2 * batch * L, "tokens/sec"
    if model_name == "word2vec":
        feeds = {n: rng.randint(0, 2000, size=(batch, 1)).astype("int64")
                 for n in ("firstw", "secondw", "thirdw", "fourthw", "nextw")}
        return feeds, batch, "samples/sec"
    if model_name == "deepfm":
        return {"feat_ids": rng.randint(0, 1000, size=(batch, 26)).astype("int64"),
                "label": rng.randint(0, 2, size=(batch, 1)).astype("float32")}, batch, "samples/sec"
    if model_name == "ocr_crnn_ctc":
        lens = rng.randint(2, 6, size=(batch,)).astype(np.int32)
        lab = rng.randint(0, 95, size=(batch, 8)).astype("int64")
        return {"pixel": rng.randn(batch, 1, 48, 384).astype("float32"),
                "label": LoDArray(lab, lens)}, batch, "images/sec"
    if model_name == "recommender":
        # ranges come from the dataset the model sizes its tables with
        from paddle_tpu.dataset import movielens as ml

        T_cat, T_title = 3, 6
        lens_c = rng.randint(1, T_cat + 1, size=(batch,)).astype(np.int32)
        lens_t = rng.randint(2, T_title + 1, size=(batch,)).astype(np.int32)
        return {"user_id": rng.randint(1, ml.max_user_id() + 1, size=(batch, 1)).astype("int64"),
                "gender_id": rng.randint(0, 2, size=(batch, 1)).astype("int64"),
                "age_id": rng.randint(0, 7, size=(batch, 1)).astype("int64"),
                "job_id": rng.randint(0, ml.max_job_id() + 1, size=(batch, 1)).astype("int64"),
                "movie_id": rng.randint(1, ml.max_movie_id() + 1, size=(batch, 1)).astype("int64"),
                "category_id": LoDArray(rng.randint(0, len(ml.movie_categories()), size=(batch, T_cat, 1)).astype("int64"), lens_c),
                "movie_title": LoDArray(rng.randint(0, len(ml.get_movie_title_dict()), size=(batch, T_title, 1)).astype("int64"), lens_t),
                "score": rng.randint(1, 6, size=(batch, 1)).astype("float32")}, batch, "samples/sec"
    if model_name == "label_semantic_roles":
        T = 20
        lens = rng.randint(5, T + 1, size=(batch,)).astype(np.int32)
        def seq():
            return LoDArray(rng.randint(0, 200, size=(batch, T, 1)).astype("int64"), lens)
        feeds = {n: seq() for n in ("word", "ctx_n2", "ctx_n1", "ctx_0", "ctx_p1", "ctx_p2")}
        feeds["mark"] = LoDArray(rng.randint(0, 2, size=(batch, T, 1)).astype("int64"), lens)
        feeds["target"] = LoDArray(rng.randint(0, 11, size=(batch, T, 1)).astype("int64"), lens)
        return feeds, int(lens.sum()), "tokens/sec"
    if model_name == "ssd":
        G = 8
        lens = rng.randint(1, G, size=(batch,)).astype(np.int32)
        boxes = np.sort(rng.rand(batch, G, 2, 2), axis=2).reshape(batch, G, 4).astype("float32")
        labels = rng.randint(1, 21, size=(batch, G)).astype("int64")
        return {"image": rng.rand(batch, 3, 300, 300).astype("float32"),
                "gt_box": LoDArray(boxes, lens), "gt_label": LoDArray(labels, lens)}, batch, "images/sec"
    raise ValueError(model_name)


def build(model_name, batch, on_tpu):
    import paddle_tpu as fluid
    from paddle_tpu import models as zoo

    dtype = "bfloat16" if on_tpu else "float32"
    with fluid.unique_name.guard():
        if model_name == "mnist":
            return zoo.mnist.get_model()
        if model_name == "vgg16":
            return zoo.vgg.get_model(batch_size=batch)
        if model_name == "resnet50":
            return dict(zoo.resnet.get_model(batch_size=batch, dtype=dtype), image_shape=(3, 224, 224))
        if model_name == "se_resnext":
            return zoo.se_resnext.get_model(batch_size=batch)
        if model_name == "stacked_dynamic_lstm":
            return zoo.stacked_dynamic_lstm.get_model(batch_size=batch)
        if model_name == "transformer":
            L = 256 if on_tpu else 32
            return dict(zoo.transformer.get_model(batch_size=batch, seq_len=L, use_flash=on_tpu), seq_len=L)
        if model_name == "word2vec":
            return zoo.word2vec.get_model()
        if model_name == "deepfm":
            return zoo.deepfm.get_model()
        if model_name == "ocr_crnn_ctc":
            return zoo.ocr_crnn_ctc.get_model()
        if model_name == "ssd":
            return zoo.ssd.get_model()
        if model_name == "recommender":
            return zoo.recommender.get_model()
        if model_name == "label_semantic_roles":
            return zoo.label_semantic_roles.get_model(depth=2, hidden_dim=64)
    raise ValueError(model_name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batch_size", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--skip_first", type=int, default=3)
    ap.add_argument("--real_data", action="store_true",
                    help="feed image models from the real input pipeline "
                         "(decoded uint8 recordio; image models only)")
    args = ap.parse_args()

    import paddle_tpu as fluid

    on_tpu = _on_tpu()
    defaults = {"resnet50": 128, "vgg16": 64, "se_resnext": 64, "transformer": 64,
                "stacked_dynamic_lstm": 64, "mnist": 256, "word2vec": 512,
                "deepfm": 512, "ocr_crnn_ctc": 32, "ssd": 16,
                "recommender": 256, "label_semantic_roles": 64}
    batch = args.batch_size or (defaults.get(args.model, 64) if on_tpu else 4)
    iters = args.iters or (30 if on_tpu else 3)

    model = build(args.model, batch, on_tpu)
    rng = np.random.RandomState(0)
    feeds, units, unit_name = _synth(args.model, model, batch, rng)

    next_feed = lambda: feeds  # noqa: E731
    if args.real_data:
        # each image model's true input contract: (feed name, CHW shape,
        # class count) straight from its data layer / get_model defaults
        contracts = {
            "mnist": ("pixel", (1, 28, 28), 10),
            "vgg16": ("pixel", (3, 32, 32), 10),
            "resnet50": ("data", (3, 224, 224), 1000),
            "se_resnext": ("data", (3, 224, 224), 1000),
        }
        if args.model not in contracts:
            raise SystemExit("--real_data supports image models only")
        img_key, shape, n_classes = contracts[args.model]
        import tempfile

        from paddle_tpu.reader.image_pipeline import (
            batched_images, convert_decoded_to_recordio, decoded_pipeline,
            synthesize_jpeg_corpus, normalize_batch)

        size = shape[1]
        d = tempfile.mkdtemp(prefix="fb_real_")
        samples = synthesize_jpeg_corpus(d, n=max(256, 2 * batch),
                                         size=size + 32, classes=n_classes)
        shards = convert_decoded_to_recordio(
            samples, os.path.join(d, "dec"), stored_size=size + 32)
        reader = decoded_pipeline(shards, mode="train", image_size=size,
                                  epochs=10_000, output="uint8")
        batches = batched_images(reader, batch)()

        def next_feed():
            imgs, labels = next(batches)
            x = normalize_batch(imgs)
            if shape[0] == 1:  # grayscale model: luminance channel
                x = x.mean(axis=1, keepdims=True)
            return {img_key: x.astype("float32"), "label": labels % n_classes}

    from paddle_tpu.executor import Executor

    exe = Executor(fluid.TPUPlace() if on_tpu else fluid.CPUPlace())
    # go through the executor so LoD feeds and caching work uniformly
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(model["startup"], scope=scope)
        for _ in range(args.skip_first):
            exe.run(model["main"], feed=next_feed(), fetch_list=[model["loss"]], scope=scope)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = exe.run(model["main"], feed=next_feed(), fetch_list=[model["loss"]], scope=scope)
        np.asarray(out[0])
        dt = time.perf_counter() - t0

    rate = units * iters / dt
    print(json.dumps({
        "model": args.model,
        "batch_size": batch,
        "iters": iters,
        "metric": "%s_%s" % (args.model, unit_name.replace("/", "_per_")),
        "value": round(rate, 2),
        "unit": unit_name,
    }))


if __name__ == "__main__":
    main()
