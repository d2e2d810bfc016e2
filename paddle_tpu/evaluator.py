"""Evaluator framework — the reference's ``paddle/gserver/evaluators``
(Evaluator.cpp: classification_error:995, sum:996, column_sum, rankauc:503,
precision_recall:584, pnpair:862; ChunkEvaluator.cpp:288;
CTCErrorEvaluator.cpp:277; printers :1009-1346) exposed with the
trainer_config_helpers/evaluators.py surface.

TPU-native split: each evaluator contributes
  * an **in-graph update** — pure jnp over the step's layer outputs producing
    fixed-shape accumulator arrays (no host sync, fuses into the step), and
  * a **host finalize** — turns summed accumulators into scalar results.
The trainer sums accumulators across batches (per-batch for iteration events,
per-pass for pass events) and calls finalize for display — replacing the
reference's start()/eval()/finish() object protocol with pure data.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.topology import LayerOutput, auto_name

Accums = Dict[str, jnp.ndarray]


@dataclasses.dataclass
class Evaluator:
    name: str
    layers: List[LayerOutput]  # outputs the in-graph update needs
    update: Callable[[Dict[str, SeqTensor]], Accums]
    finalize: Callable[[Dict[str, object]], Dict[str, float]]


def _ids_of(t: SeqTensor) -> jnp.ndarray:
    ids = t.data.astype(jnp.int32)
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    return ids


def _flat_valid(pred: SeqTensor, label: SeqTensor):
    """(pred2d [N, C], ids [N], weight [N]) flattening sequence time."""
    p = pred.data
    ids = _ids_of(label)
    if pred.is_seq and p.ndim == 3:
        w = pred.mask().reshape(-1)
        return p.reshape(-1, p.shape[-1]), ids.reshape(-1), w
    return p, ids.reshape(-1), jnp.ones((p.shape[0],), jnp.float32)


# ---------------------------------------------------------------------------
# classification_error
# ---------------------------------------------------------------------------


def classification_error_evaluator(
    input: LayerOutput, label: LayerOutput, name: Optional[str] = None
) -> Evaluator:
    nm = name or auto_name("classification_error")

    def update(outs):
        # argmax(softmax(x)) == argmax(x): prefer the producer's
        # pre-activation aux so the error never forces a big softmax to
        # materialize (the fused CE path reads logits directly)
        pred = outs.get(input.name + "@logits")
        if pred is None:
            pred = outs[input.name]
        p, ids, w = _flat_valid(pred, outs[label.name])
        err = (jnp.argmax(p, axis=-1) != ids).astype(jnp.float32)
        return {"err": jnp.sum(err * w), "total": jnp.sum(w)}

    def finalize(acc):
        return {nm: float(acc["err"]) / max(float(acc["total"]), 1.0)}

    return Evaluator(nm, [input, label], update, finalize)


# ---------------------------------------------------------------------------
# sum / column_sum
# ---------------------------------------------------------------------------


def sum_evaluator(input: LayerOutput, name: Optional[str] = None) -> Evaluator:
    nm = name or auto_name("sum")

    def update(outs):
        t = outs[input.name]
        return {"sum": jnp.sum(t.masked_data() if t.is_seq else t.data)}

    return Evaluator(nm, [input], update, lambda a: {nm: float(a["sum"])})


def column_sum_evaluator(
    input: LayerOutput, name: Optional[str] = None
) -> Evaluator:
    nm = name or auto_name("column_sum")

    def update(outs):
        t = outs[input.name]
        d = t.masked_data() if t.is_seq else t.data
        return {"colsum": jnp.sum(d.reshape(-1, d.shape[-1]), axis=0),
                "n": jnp.asarray(d.reshape(-1, d.shape[-1]).shape[0], jnp.float32)}

    def finalize(acc):
        import numpy as np

        col = np.asarray(acc["colsum"]) / max(float(acc["n"]), 1.0)
        return {f"{nm}[{i}]": float(v) for i, v in enumerate(col)}

    return Evaluator(nm, [input], update, finalize)


# ---------------------------------------------------------------------------
# auc — histogram-based rank AUC (reference AucEvaluator sorts on host; a
# fixed-bin histogram gives the same statistic with static shapes on device)
# ---------------------------------------------------------------------------


def auc_evaluator(
    input: LayerOutput, label: LayerOutput, name: Optional[str] = None,
    num_bins: int = 4096,
) -> Evaluator:
    nm = name or auto_name("auc")

    def update(outs):
        p, ids, w = _flat_valid(outs[input.name], outs[label.name])
        # positive-class score: column 1 of a 2-col softmax, else column 0
        score = p[:, 1] if p.shape[-1] >= 2 else p[:, 0]
        bin_ = jnp.clip((score * num_bins).astype(jnp.int32), 0, num_bins - 1)
        pos = jnp.zeros((num_bins,)).at[bin_].add(w * (ids == 1))
        neg = jnp.zeros((num_bins,)).at[bin_].add(w * (ids != 1))
        return {"pos": pos, "neg": neg}

    def finalize(acc):
        import numpy as np

        pos = np.asarray(acc["pos"], np.float64)
        neg = np.asarray(acc["neg"], np.float64)
        # walk bins from high score to low, trapezoid on the ROC curve
        tp = np.cumsum(pos[::-1])
        fp = np.cumsum(neg[::-1])
        tot_p, tot_n = tp[-1], fp[-1]
        if tot_p == 0 or tot_n == 0:
            return {nm: 0.0}
        tpr = np.concatenate([[0.0], tp / tot_p])
        fpr = np.concatenate([[0.0], fp / tot_n])
        return {nm: float(np.trapezoid(tpr, fpr))}

    return Evaluator(nm, [input, label], update, finalize)


# ---------------------------------------------------------------------------
# precision_recall
# ---------------------------------------------------------------------------


def precision_recall_evaluator(
    input: LayerOutput, label: LayerOutput,
    positive_label: int = -1, name: Optional[str] = None,
) -> Evaluator:
    nm = name or auto_name("precision_recall")
    c = input.size

    def update(outs):
        p, ids, w = _flat_valid(outs[input.name], outs[label.name])
        pred = jnp.argmax(p, axis=-1)
        onehot_pred = jax.nn.one_hot(pred, c) * w[:, None]
        onehot_gold = jax.nn.one_hot(ids, c) * w[:, None]
        tp = jnp.sum(onehot_pred * onehot_gold, axis=0)
        return {
            "tp": tp,
            "pred": jnp.sum(onehot_pred, axis=0),
            "gold": jnp.sum(onehot_gold, axis=0),
        }

    def finalize(acc):
        import numpy as np

        tp = np.asarray(acc["tp"], np.float64)
        pred = np.asarray(acc["pred"], np.float64)
        gold = np.asarray(acc["gold"], np.float64)
        if positive_label >= 0:
            sel = [positive_label]
        else:
            sel = list(range(c))
        precs = [tp[i] / pred[i] if pred[i] else 0.0 for i in sel]
        recs = [tp[i] / gold[i] if gold[i] else 0.0 for i in sel]
        prec, rec = float(np.mean(precs)), float(np.mean(recs))
        f1 = 2 * prec * rec / (prec + rec) if prec + rec else 0.0
        return {f"{nm}.precision": prec, f"{nm}.recall": rec, f"{nm}.F1": f1}

    return Evaluator(nm, [input, label], update, finalize)


# ---------------------------------------------------------------------------
# pnpair — positive-negative pair ratio within query groups
# ---------------------------------------------------------------------------


def pnpair_evaluator(
    input: LayerOutput, label: LayerOutput, query_id: LayerOutput,
    name: Optional[str] = None,
) -> Evaluator:
    nm = name or auto_name("pnpair")

    def update(outs):
        score_t = outs[input.name]
        score = score_t.data.reshape(-1)
        y = _ids_of(outs[label.name]).reshape(-1).astype(jnp.float32)
        q = _ids_of(outs[query_id.name]).reshape(-1)
        if score_t.is_seq:
            w = score_t.mask(bool).reshape(-1)
        else:
            w = jnp.ones(score.shape, bool)
        same_q = q[:, None] == q[None, :]
        better = y[:, None] > y[None, :]
        mask = same_q & better & w[:, None] & w[None, :]
        sdiff = score[:, None] - score[None, :]
        pos = jnp.sum(mask & (sdiff > 0))
        neg = jnp.sum(mask & (sdiff < 0))
        spe = jnp.sum(mask & (sdiff == 0))
        return {"pos": pos.astype(jnp.float32),
                "neg": neg.astype(jnp.float32),
                "spe": spe.astype(jnp.float32)}

    def finalize(acc):
        pos, neg, spe = (float(acc[k]) for k in ("pos", "neg", "spe"))
        return {nm: (pos + 0.5 * spe) / max(neg + 0.5 * spe, 1e-12)}

    return Evaluator(nm, [input, label, query_id], update, finalize)


# ---------------------------------------------------------------------------
# ctc_error — edit distance between best-path CTC decode and the label
# ---------------------------------------------------------------------------


def _ctc_best_path(logits: jnp.ndarray, lengths: jnp.ndarray, blank: int):
    """Greedy decode + collapse → (padded ids [B, T], lens [B])."""
    b_, t_ = logits.shape[0], logits.shape[1]
    am = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, T]
    prev = jnp.pad(am, ((0, 0), (1, 0)), constant_values=-1)[:, :t_]
    tpos = jnp.arange(t_)[None, :]
    keep = (am != blank) & (am != prev) & (tpos < lengths[:, None])
    # stable-compact kept symbols to the front
    order = jnp.argsort(~keep, axis=1, stable=True)
    out = jnp.take_along_axis(am, order, axis=1)
    return out, jnp.sum(keep, axis=1).astype(jnp.int32)


def _edit_distance(a, alen, b, blen):
    """Batched Levenshtein via scan over a's positions. a: [B, Ta], b: [B, Tb]."""
    b_, ta = a.shape
    tb = b.shape[1]
    # row[j] = distance(a[:i], b[:j]); freeze once i > alen
    init = jnp.broadcast_to(jnp.arange(tb + 1, dtype=jnp.float32), (b_, tb + 1))

    def step(row, inp):
        ai, i = inp  # [B], scalar
        sub = (a[:, i][:, None] != b).astype(jnp.float32)  # [B, Tb]
        new = jnp.zeros_like(row).at[:, 0].set(i + 1.0)

        def inner(left, j):
            val = jnp.minimum(
                jnp.minimum(row[:, j + 1] + 1.0, left + 1.0),
                row[:, j] + sub[:, j],
            )
            return val, val

        _, cols = jax.lax.scan(inner, new[:, 0], jnp.arange(tb))
        new = jnp.concatenate([new[:, :1], jnp.moveaxis(cols, 0, 1)], axis=1)
        active = (i < alen)[:, None]
        return jnp.where(active, new, row), None

    row, _ = jax.lax.scan(step, init, (jnp.moveaxis(a, 1, 0), jnp.arange(ta)))
    return jnp.take_along_axis(row, blen[:, None], axis=1)[:, 0]


def ctc_error_evaluator(
    input: LayerOutput, label: LayerOutput, blank: int = 0,
    name: Optional[str] = None,
) -> Evaluator:
    """Sequence error = edit_distance(best-path decode, label) / label_len
    (reference CTCErrorEvaluator.cpp:277)."""
    nm = name or auto_name("ctc_error")

    def update(outs):
        pred_t, lab_t = outs[input.name], outs[label.name]
        dec, dec_len = _ctc_best_path(pred_t.data, pred_t.lengths, blank)
        lab = _ids_of(lab_t)
        dist = _edit_distance(dec, dec_len, lab, lab_t.lengths)
        return {
            "dist": jnp.sum(dist),
            "ref": jnp.sum(lab_t.lengths).astype(jnp.float32),
            "seqs": jnp.asarray(dec.shape[0], jnp.float32),
        }

    def finalize(acc):
        return {nm: float(acc["dist"]) / max(float(acc["ref"]), 1.0)}

    return Evaluator(nm, [input, label], update, finalize)


# ---------------------------------------------------------------------------
# chunk — F1 over chunk segmentations (reference ChunkEvaluator.cpp:288)
# label encoding: id = chunk_type * tag_num + tag, O = num_chunk_types*tag_num
# ---------------------------------------------------------------------------

_SCHEMES = {
    # tag ids within a type
    "plain": {"num": 1},
    "IOB": {"num": 2, "B": 0, "I": 1},
    "IOE": {"num": 2, "I": 0, "E": 1},
    "IOBES": {"num": 4, "B": 0, "I": 1, "E": 2, "S": 3},
}


def _chunk_bounds(ids, lengths, scheme: str, num_types: int):
    """(start [B,T] bool, end [B,T] bool, type [B,T]) per position."""
    sc = _SCHEMES[scheme]
    tag_num = sc["num"]
    o_id = num_types * tag_num
    is_o = ids >= o_id
    typ = jnp.where(is_o, -1, ids // tag_num)
    tag = jnp.where(is_o, -1, ids % tag_num)

    t_ = ids.shape[1]
    tpos = jnp.arange(t_)[None, :]
    valid = tpos < lengths[:, None]
    prev_typ = jnp.pad(typ, ((0, 0), (1, 0)), constant_values=-1)[:, :t_]
    prev_tag = jnp.pad(tag, ((0, 0), (1, 0)), constant_values=-1)[:, :t_]
    next_typ = jnp.pad(typ, ((0, 0), (0, 1)), constant_values=-1)[:, 1:]
    next_tag = jnp.pad(tag, ((0, 0), (0, 1)), constant_values=-1)[:, 1:]
    last_pos = tpos == (lengths[:, None] - 1)
    next_typ = jnp.where(last_pos, -1, next_typ)
    next_tag = jnp.where(last_pos, -1, next_tag)
    first_pos = tpos == 0
    in_chunk = (~is_o) & valid

    if scheme == "plain":
        start = in_chunk & (typ != prev_typ)
        end = in_chunk & (typ != next_typ)
    elif scheme == "IOB":
        start = in_chunk & (
            (tag == sc["B"])
            | ((tag == sc["I"]) & ((prev_typ != typ) | first_pos))
        )
        end = in_chunk & (
            (next_typ != typ) | (next_tag == sc["B"]) | last_pos
        )
    elif scheme == "IOE":
        start = in_chunk & ((prev_typ != typ) | (prev_tag == sc["E"]) | first_pos)
        end = in_chunk & ((tag == sc["E"]) | (next_typ != typ) | last_pos)
    else:  # IOBES
        start = in_chunk & ((tag == sc["B"]) | (tag == sc["S"]))
        end = in_chunk & ((tag == sc["E"]) | (tag == sc["S"]))
    return start & valid, end & valid, typ


def _next_end_pos(end):
    """[B, T] int: for each position, index of the next end >= it (T if none)."""
    b_, t_ = end.shape
    idx = jnp.where(end, jnp.arange(t_)[None, :], t_)
    # reverse cumulative min
    return jnp.flip(jax.lax.cummin(jnp.flip(idx, axis=1), axis=1), axis=1)


def chunk_evaluator(
    input: LayerOutput, label: LayerOutput,
    chunk_scheme: str = "IOB", num_chunk_types: int = 1,
    name: Optional[str] = None,
) -> Evaluator:
    nm = name or auto_name("chunk")

    def update(outs):
        pred_t, lab_t = outs[input.name], outs[label.name]
        lengths = lab_t.lengths
        pred = _ids_of(pred_t)
        gold = _ids_of(lab_t)
        ps, pe, pt = _chunk_bounds(pred, lengths, chunk_scheme, num_chunk_types)
        gs, ge, gt = _chunk_bounds(gold, lengths, chunk_scheme, num_chunk_types)
        p_end = _next_end_pos(pe)
        g_end = _next_end_pos(ge)
        correct = ps & gs & (pt == gt) & (p_end == g_end)
        return {
            "correct": jnp.sum(correct).astype(jnp.float32),
            "pred": jnp.sum(ps).astype(jnp.float32),
            "gold": jnp.sum(gs).astype(jnp.float32),
        }

    def finalize(acc):
        c, p, g = float(acc["correct"]), float(acc["pred"]), float(acc["gold"])
        prec = c / p if p else 0.0
        rec = c / g if g else 0.0
        f1 = 2 * prec * rec / (prec + rec) if prec + rec else 0.0
        return {f"{nm}.precision": prec, f"{nm}.recall": rec, f"{nm}.F1": f1}

    return Evaluator(nm, [input, label], update, finalize)


# ---------------------------------------------------------------------------
# printers — side-effect evaluators (reference value/maxid/seqtext printers)
# ---------------------------------------------------------------------------


def value_printer_evaluator(input: LayerOutput, name: Optional[str] = None) -> Evaluator:
    nm = name or auto_name("value_printer")

    def update(outs):
        jax.debug.print(nm + " {v}", v=outs[input.name].data)
        return {}

    return Evaluator(nm, [input], update, lambda a: {})


def maxid_printer_evaluator(input: LayerOutput, name: Optional[str] = None) -> Evaluator:
    nm = name or auto_name("maxid_printer")

    def update(outs):
        jax.debug.print(nm + " {v}", v=jnp.argmax(outs[input.name].data, axis=-1))
        return {}

    return Evaluator(nm, [input], update, lambda a: {})


def seq_text_printer_evaluator(
    input: LayerOutput,
    id_to_word=None,
    result_file: Optional[str] = None,
    id_input: Optional[LayerOutput] = None,
    dict_file: Optional[str] = None,
    name: Optional[str] = None,
) -> Evaluator:
    """Print id sequences as text (reference seqtext_printer_evaluator,
    trainer_config_helpers/evaluators.py: dict_file + result_file).
    `id_to_word` maps id→token (dict/list/callable); None prints raw ids.
    `dict_file` loads that mapping one token per line (the reference's
    surface); `id_input` (reference: separate id stream alongside the text
    stream) is accepted — the ids printed are the input layer's.
    The print runs host-side via io_callback so it works under jit."""
    nm = name or auto_name("seq_text_printer")
    if id_to_word is None and dict_file:
        with open(dict_file) as f:
            id_to_word = [ln.rstrip("\n").split("\t")[0] for ln in f]

    def to_text(ids, lengths):
        import numpy as np

        lines = []
        ids = np.asarray(ids)
        lengths = None if lengths is None else np.asarray(lengths)
        for i in range(ids.shape[0]):
            row = ids[i][: int(lengths[i])] if lengths is not None else ids[i]
            if id_to_word is None:
                toks = [str(int(t)) for t in row.reshape(-1)]
            elif callable(id_to_word):
                toks = [str(id_to_word(int(t))) for t in row.reshape(-1)]
            else:
                toks = [str(id_to_word[int(t)]) for t in row.reshape(-1)]
            lines.append(" ".join(toks))
        text = "\n".join(lines)
        if result_file:
            with open(result_file, "a") as f:
                f.write(text + "\n")
        else:
            print(f"{nm}:\n{text}")

    def update(outs):
        t = outs[input.name]
        if t.is_seq:
            jax.experimental.io_callback(
                to_text, None, t.data, t.lengths, ordered=True
            )
        else:
            jax.experimental.io_callback(to_text, None, t.data, None, ordered=True)
        return {}

    return Evaluator(nm, [input], update, lambda a: {})


def maxframe_printer_evaluator(
    input: LayerOutput, name: Optional[str] = None
) -> Evaluator:
    """Print, per sample, the FRAME (timestep) holding the maximum
    activation and that value (reference max_frame_printer,
    Evaluator.cpp:1061 MaxFramePrinter — prints the max-value frame of
    each sequence).  Non-sequence inputs degenerate to the per-sample max
    feature.  Runs host-side via io_callback so it works under jit."""
    nm = name or auto_name("maxframe_printer")

    def to_text(data, lengths):
        import numpy as np

        data = np.asarray(data)
        lengths = None if lengths is None else np.asarray(lengths)
        lines = []
        for i in range(data.shape[0]):
            row = data[i]
            if lengths is not None:
                row = row[: max(int(lengths[i]), 1)]
            flat = row.reshape(row.shape[0], -1) if row.ndim > 1 else (
                row.reshape(-1, 1)
            )
            per_frame = flat.max(axis=-1)
            frame = int(np.argmax(per_frame))
            lines.append(
                f"sample {i}: frame {frame} value {float(per_frame[frame]):.6g}"
            )
        print(f"{nm}:\n" + "\n".join(lines))

    def update(outs):
        t = outs[input.name]
        jax.experimental.io_callback(
            to_text, None, t.data,
            t.lengths if t.is_seq else None, ordered=True,
        )
        return {}

    return Evaluator(nm, [input], update, lambda a: {})


def classification_error_printer_evaluator(
    input: LayerOutput, label: LayerOutput, name: Optional[str] = None
) -> Evaluator:
    """Print the PER-INSTANCE classification error indicators (reference
    classification_error_printer, Evaluator.cpp:1337
    ClassificationErrorPrinter — the per-sample view of
    classification_error, printed instead of aggregated).  Sequence inputs
    print one 0/1 per valid timestep."""
    nm = name or auto_name("classification_error_printer")

    def to_text(err, w):
        import numpy as np

        err = np.asarray(err)
        w = np.asarray(w)
        vals = [
            str(int(e)) for e, ww in zip(err.reshape(-1), w.reshape(-1))
            if ww > 0
        ]
        print(f"{nm}: [" + " ".join(vals) + "]")

    def update(outs):
        pred = outs.get(input.name + "@logits")
        if pred is None:
            pred = outs[input.name]
        p, ids, w = _flat_valid(pred, outs[label.name])
        err = (jnp.argmax(p, axis=-1) != ids).astype(jnp.float32)
        jax.experimental.io_callback(to_text, None, err, w, ordered=True)
        return {}

    return Evaluator(nm, [input, label], update, lambda a: {})


def gradient_printer_evaluator(
    input: LayerOutput, name: Optional[str] = None
) -> Evaluator:
    """reference gradient_printer_evaluator prints a layer's output
    gradient mid-backward.  Backward here is one jax.grad over the whole
    step, so the per-layer output gradient is not materialized in the
    evaluator's (forward) view — the equivalent diagnostic is
    utils.debug.gradient_stats, which computes per-parameter gradient norms
    with a dedicated jax.grad.  This evaluator prints the layer's forward
    VALUE norm so v1 configs still run, and points at gradient_stats."""
    nm = name or auto_name("gradient_printer")

    def update(outs):
        v = outs[input.name].data
        jax.debug.print(
            nm + " forward-norm {n} (use utils.debug.gradient_stats for "
            "gradient norms)", n=jnp.linalg.norm(v.astype(jnp.float32)),
        )
        return {}

    return Evaluator(nm, [input], update, lambda a: {})


# ---------------------------------------------------------------------------
# detection mAP (reference DetectionMAPEvaluator.cpp:306)
# ---------------------------------------------------------------------------

_MAP_BINS = 1000


def detection_map_evaluator(
    input: LayerOutput,  # detection_output layer: [B, K, 6]
    label: LayerOutput,  # gt slot: [B, G, 6] (label,x1,y1,x2,y2,difficult)
    num_classes: int,
    overlap_threshold: float = 0.5,
    background_id: int = 0,
    evaluate_difficult: bool = False,
    ap_type: str = "11point",
    name: Optional[str] = None,
) -> Evaluator:
    """Streaming mAP: the in-graph update greedily matches each image's
    detections to ground truth (sorted by score, one gt per detection,
    IoU >= threshold) and accumulates TP/FP counts into per-class score-bin
    histograms; finalize integrates the binned PR curve on the host
    (11-point interpolation or trapezoid 'Integral', matching the
    reference's two ap_type modes).  The reference buffers every
    (score, tp/fp) pair on the host instead — binning keeps the accumulator
    static-shape for jit, at <=1/NBINS score resolution."""
    import jax

    from paddle_tpu.ops.detection import iou_matrix

    nm = name or auto_name("detection_map")

    def update(outs):
        det_t, gt_t = outs[input.name], outs[label.name]
        det = det_t.data  # [B, K, 6]
        gt = gt_t.data  # [B, G, 6]
        gt_valid = gt_t.mask(jnp.float32) > 0 if gt_t.is_seq else (
            jnp.ones(gt.shape[:2], bool)
        )

        def per_image(det_i, gt_i, valid_i):
            g_lab = gt_i[:, 0].astype(jnp.int32)
            g_box = gt_i[:, 1:5]
            g_diff = gt_i[:, 5] > 0
            counted = valid_i & (evaluate_difficult | ~g_diff)
            n_gt = jnp.zeros((num_classes,), jnp.float32).at[g_lab].add(
                counted.astype(jnp.float32)
            )
            # sort detections by score desc (detection_output emits top-k
            # globally sorted, but per-class order must be by score)
            order = jnp.argsort(-det_i[:, 1])
            det_i = det_i[order]
            d_lab = det_i[:, 0].astype(jnp.int32)
            d_score = det_i[:, 1]
            d_box = det_i[:, 2:6]
            ious = iou_matrix(d_box, g_box)  # [K, G]

            def body(used, k):
                lab, score, iou_k = d_lab[k], d_score[k], ious[k]
                # Reference calcTFPos: best-overlap gt over ALL same-class
                # gts (visited or not); a hit on a visited gt is an FP, a
                # hit on a skipped difficult gt is ignored and does NOT mark
                # the gt visited.
                cand = valid_i & (g_lab == lab)
                masked = jnp.where(cand, iou_k, -1.0)
                best = jnp.argmax(masked)
                hit = masked[best] >= overlap_threshold
                live = (lab >= 0) & (lab != background_id) & (score > 0)
                ignore = hit & g_diff[best] & (not evaluate_difficult)
                already = used[best]
                tp = live & hit & ~ignore & ~already
                fp = live & ((~hit) | (hit & ~ignore & already))
                used = used.at[best].set(already | (hit & live & ~ignore))
                bin_ = jnp.clip(
                    (score * _MAP_BINS).astype(jnp.int32), 0, _MAP_BINS - 1
                )
                return used, (lab, bin_, tp, fp)

            used0 = jnp.zeros(g_lab.shape, bool)
            _, (labs, bins, tps, fps) = jax.lax.scan(
                body, used0, jnp.arange(det_i.shape[0])
            )
            safe_lab = jnp.clip(labs, 0, num_classes - 1)
            tp_h = jnp.zeros((num_classes, _MAP_BINS), jnp.float32).at[
                safe_lab, bins
            ].add(tps.astype(jnp.float32))
            fp_h = jnp.zeros((num_classes, _MAP_BINS), jnp.float32).at[
                safe_lab, bins
            ].add(fps.astype(jnp.float32))
            return n_gt, tp_h, fp_h

        n_gt, tp_h, fp_h = jax.vmap(per_image)(det, gt, gt_valid)
        return {
            "n_gt": jnp.sum(n_gt, 0),
            "tp": jnp.sum(tp_h, 0),
            "fp": jnp.sum(fp_h, 0),
        }

    def finalize(acc):
        import numpy as np

        n_gt = np.asarray(acc["n_gt"])
        tp = np.asarray(acc["tp"])[:, ::-1]  # high-score bins first
        fp = np.asarray(acc["fp"])[:, ::-1]
        aps = []
        for c in range(num_classes):
            if c == background_id or n_gt[c] <= 0:
                continue
            ctp, cfp = np.cumsum(tp[c]), np.cumsum(fp[c])
            recall = ctp / n_gt[c]
            precision = ctp / np.maximum(ctp + cfp, 1e-10)
            if ap_type == "11point":
                ap = 0.0
                for t in np.linspace(0, 1, 11):
                    mask = recall >= t
                    ap += (precision[mask].max() if mask.any() else 0.0) / 11.0
            else:  # Integral: sum precision deltas over recall steps
                prev_r = 0.0
                ap = 0.0
                for r, p in zip(recall, precision):
                    ap += (r - prev_r) * p
                    prev_r = r
            aps.append(ap)
        return {nm: float(np.mean(aps)) if aps else 0.0}

    return Evaluator(nm, [input, label], update, finalize)


# ---------------------------------------------------------------------------
# combination helpers (used by the trainer)
# ---------------------------------------------------------------------------


def combined_update(evaluators: Sequence[Evaluator]):
    """One in-graph fn emitting all accumulators, namespaced per evaluator."""

    def update(outs) -> Accums:
        acc: Accums = {}
        for ev in evaluators:
            with jax.named_scope(f"evaluator:{ev.name}"):
                updates = ev.update(outs)
            for k, v in updates.items():
                acc[f"ev:{ev.name}:{k}"] = v
        return acc

    return update


def finalize_all(evaluators: Sequence[Evaluator], sums: Dict[str, object]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for ev in evaluators:
        prefix = f"ev:{ev.name}:"
        acc = {k[len(prefix):]: v for k, v in sums.items() if k.startswith(prefix)}
        if acc or not ev.layers:
            out.update(ev.finalize(acc))
    return out
