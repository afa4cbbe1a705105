"""Wide&Deep / DeepFM CTR models (BASELINE config 4).

Parity target: reference CTR models (dist_ctr.py / ctr_dataset_reader in
python/paddle/fluid/tests/unittests/, pslib Downpour sparse-PS path).
TPU-first: the distributed lookup table (remote prefetch RPC,
operators/distributed/parameter_prefetch.h:26) becomes a single dense
embedding table sharded over the "mp" mesh axis along the vocab dim — the
EP-style sharding; XLA turns the sharded gather into an all-to-all-style
exchange over ICI (SURVEY §2.3 row "Parameter prefetch").

Inputs are dense [B, num_slots] int32 slot ids (pre-hashed into a shared
id space host-side — the dense-padding answer to sparse LoD slots).
"""
from __future__ import annotations

from .. import layers
from ..param_attr import ParamAttr
from ..initializer import Normal, Constant, Uniform


def wide_deep(slot_ids, dense_feat, vocab_size=1000001, embed_dim=16,
              deep_layers=(400, 400, 400), is_sparse=False):
    """slot_ids: [B, num_slots] int32; dense_feat: [B, num_dense] f32.
    Returns logit [B, 1]."""
    # deep: shared embedding table, slots looked up together then flattened
    # is_sparse defaults to FALSE on TPU, the opposite of the
    # reference's Downpour instinct (fleet_wrapper.h:55) — measured
    # r4 A/B at B=4096/1M vocab: dense grads 243.6k examples/s vs
    # SelectedRows 154.5k. The dense [vocab, dim] grad + full-table
    # Adagrad pass is ~0.5 GB of clean streaming traffic (measured
    # 3.5 ms per 64 MB read+write pass; July 2026, previous
    # installation, git history), while the sparse path's scatter-add
    # serializes on TPU (~15M rows/s). Set is_sparse=True when the
    # table cannot afford a dense optimizer pass (multi-GB vocabs).
    emb = layers.embedding(
        slot_ids, size=[vocab_size, embed_dim], is_sparse=is_sparse,
        param_attr=ParamAttr(name="ctr_emb.w_0",
                             initializer=Normal(0.0, 0.01)))
    deep = layers.flatten(emb, axis=1)
    if dense_feat is not None:
        deep = layers.concat([deep, dense_feat], axis=1)
    for i, width in enumerate(deep_layers):
        deep = layers.fc(deep, width, act="relu",
                         param_attr=ParamAttr(name=f"ctr_deep_{i}.w_0"),
                         bias_attr=ParamAttr(name=f"ctr_deep_{i}.b_0"))
    deep_logit = layers.fc(deep, 1,
                           param_attr=ParamAttr(name="ctr_deep_out.w_0"),
                           bias_attr=ParamAttr(name="ctr_deep_out.b_0"))
    # wide: per-id scalar weight table == linear model over sparse ids
    wide_w = layers.embedding(
        slot_ids, size=[vocab_size, 1], is_sparse=is_sparse,
        param_attr=ParamAttr(name="ctr_wide.w_0",
                             initializer=Constant(0.0)))
    wide_logit = layers.reduce_sum(wide_w, dim=[1])
    if dense_feat is not None:
        wide_logit = layers.elementwise_add(
            wide_logit,
            layers.fc(dense_feat, 1,
                      param_attr=ParamAttr(name="ctr_wide_dense.w_0"),
                      bias_attr=False))
    return layers.elementwise_add(deep_logit, wide_logit)


def deepfm(slot_ids, vocab_size=1000001, embed_dim=16,
           deep_layers=(400, 400)):
    """DeepFM: first-order + FM second-order + deep tower. [B, S] ids."""
    first = layers.embedding(
        slot_ids, size=[vocab_size, 1],
        param_attr=ParamAttr(name="fm_first.w_0",
                             initializer=Constant(0.0)))
    first_logit = layers.reduce_sum(first, dim=[1])

    emb = layers.embedding(
        slot_ids, size=[vocab_size, embed_dim],
        param_attr=ParamAttr(name="fm_emb.w_0",
                             initializer=Uniform(-0.01, 0.01)))
    # FM: 0.5 * sum((sum_i v_i)^2 - sum_i v_i^2)
    sum_emb = layers.reduce_sum(emb, dim=[1])
    sum_sq = layers.elementwise_mul(sum_emb, sum_emb)
    sq = layers.elementwise_mul(emb, emb)
    sq_sum = layers.reduce_sum(sq, dim=[1])
    fm = layers.scale(layers.elementwise_sub(sum_sq, sq_sum), scale=0.5)
    fm_logit = layers.reduce_sum(fm, dim=[1], keep_dim=True)

    deep = layers.flatten(emb, axis=1)
    for i, width in enumerate(deep_layers):
        deep = layers.fc(deep, width, act="relu",
                         param_attr=ParamAttr(name=f"fm_deep_{i}.w_0"),
                         bias_attr=ParamAttr(name=f"fm_deep_{i}.b_0"))
    deep_logit = layers.fc(deep, 1,
                           param_attr=ParamAttr(name="fm_deep_out.w_0"),
                           bias_attr=ParamAttr(name="fm_deep_out.b_0"))
    return layers.elementwise_add(
        layers.elementwise_add(first_logit, fm_logit), deep_logit)


def ctr_train(model="wide_deep", vocab_size=1000001, num_slots=26,
              num_dense=13, embed_dim=16):
    """Training graph; returns (avg_cost, auc_prob, feed_names)."""
    slot_ids = layers.data("slot_ids", [-1, num_slots],
                           append_batch_size=False, dtype="int32")
    label = layers.data("ctr_label", [-1, 1], append_batch_size=False,
                        dtype="float32")
    feeds = ["slot_ids", "ctr_label"]
    if model == "wide_deep":
        dense = layers.data("dense_feat", [-1, num_dense],
                            append_batch_size=False, dtype="float32")
        feeds.insert(1, "dense_feat")
        logit = wide_deep(slot_ids, dense, vocab_size, embed_dim)
    else:
        logit = deepfm(slot_ids, vocab_size, embed_dim)
    cost = layers.sigmoid_cross_entropy_with_logits(logit, label)
    avg_cost = layers.mean(cost)
    prob = layers.sigmoid(logit)
    return avg_cost, prob, feeds
