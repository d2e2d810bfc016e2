"""Seq2seq NMT with attention — the north-star seq2seq config (BASELINE.json;
the reference era's demo/seqToseq text_generation topology: bi-GRU encoder +
attention GRU decoder, built here from the same recurrent_group/
simple_attention DSL the reference uses: trainer_config_helpers
networks.py simple_attention, layers.py recurrent_group).

Training: one jitted graph, per-step softmax CE over target vocab with
padding masked.  Generation: the decoder step sub-network is re-used as the
body of a jitted beam/greedy scan (ops/beam.py) — beam search runs on-device,
unlike the reference's host-side RecurrentGradientMachine beamSearch.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.compiler import CompiledNetwork
from paddle_tpu.core.topology import LayerOutput, Topology

L = paddle.layer
A = paddle.activation


def make_fused_step(w, enc, ep, emask, *, gate_act, act, att_act):
    """``step(ids [N], h [N,H]) -> (logp [N,V], h_t [N,H])`` over the fused
    attention-GRU decode chain — THE per-token numerical contract every
    decode face shares: the one-shot beam/greedy path here, and the
    serving plane's paged beam program (serving/engine.py) gathers its
    ``enc``/``ep`` through the page table and calls this same builder.
    One closure, one chain, bit-identity by construction.

    ``ep`` must already carry the folded state-projection bias (sp_b adds
    at prefill time, never per step); ``w`` is the
    :meth:`Seq2SeqGenerator.fused_decode_weights` bundle."""
    from paddle_tpu.ops.rnn import attention_gru_step

    def step(ids, h):
        xg = jnp.take(w["emb_w"], ids, axis=0) @ w["w_emb"]
        if w["xg_bias"] is not None:
            xg = xg + w["xg_bias"]
        h_t = attention_gru_step(
            xg, h, enc, ep, emask, w["w1"], w["v"], w["w_ctx"], w["w_c"],
            gate_act=gate_act, act=act, att_act=att_act,
        )
        logits = h_t @ w["head_w"]
        if w["head_b"] is not None:
            logits = logits + w["head_b"]
        prob = jax.nn.softmax(logits, axis=-1)
        return jnp.log(jnp.maximum(prob, 1e-9)), h_t

    return step


def encoder_net(
    src_word: LayerOutput, word_dim: int, hidden_dim: int
) -> Tuple[LayerOutput, LayerOutput]:
    """Bi-GRU encoder; returns (encoded_seq [B,S,2H], encoded_proj)."""
    emb = L.embedding(src_word, size=word_dim, name="src_emb")
    # simple_gru2: the FUSED grumemory form (one lax.scan) — same math as
    # simple_gru's recurrent_group, but the fast path for the NMT benchmark
    fwd = paddle.networks.simple_gru2(emb, size=hidden_dim, name="enc_fw")
    bwd = paddle.networks.simple_gru2(
        emb, size=hidden_dim, reverse=True, name="enc_bw"
    )
    enc = L.concat([fwd, bwd], name="enc")
    enc_proj = L.fc(
        enc, size=hidden_dim, act=A.Identity(), bias_attr=False, name="enc_proj"
    )
    return enc, enc_proj


def decoder_step_builder(hidden_dim: int, trg_vocab: int, boot: LayerOutput):
    """Returns the recurrent_group step fn used for BOTH training and
    generation — identical weights, mirroring the reference's shared
    SubModelConfig.  `boot` is an OUTER layer captured by closure (reference
    memory boot_layer semantics)."""

    def step(trg_emb_t, enc_seq, enc_p):
        state = L.memory("dec_state", hidden_dim, boot_layer=boot)
        context = paddle.networks.simple_attention(
            encoded_sequence=enc_seq,
            encoded_proj=enc_p,
            decoder_state=state,
            name="att",
        )
        inputs = L.fc(
            [context, trg_emb_t],
            size=hidden_dim * 3,
            act=A.Identity(),
            bias_attr=False,
            name="dec_in_proj",
        )
        gru = L.gru_step(inputs, state, size=hidden_dim, name="dec_state")
        out = L.fc(gru, size=trg_vocab, act=A.Softmax(), name="dec_out")
        return out

    return step


def _encoder_and_boot(src_vocab: int, word_dim: int, hidden_dim: int):
    """Shared source-side block: training and generation topologies MUST
    build these layers identically (same names, same auto-name consumption)
    for the tar parameter round-trip to map weights."""
    src = L.data("src_word", paddle.data_type.integer_value_sequence(src_vocab))
    enc, enc_proj = encoder_net(src, word_dim, hidden_dim)
    boot = L.fc(
        L.first_seq(enc, name="enc_first"),
        size=hidden_dim,
        act=A.Tanh(),
        name="dec_boot",
    )
    return enc, enc_proj, boot


def seq2seq_cost(
    src_vocab: int,
    trg_vocab: int,
    word_dim: int = 128,
    hidden_dim: int = 256,
) -> Tuple[LayerOutput, LayerOutput]:
    """Training topology.  Data slots: src_word ids, trg_word ids (bos-led),
    trg_next ids (the shifted targets)."""
    enc, enc_proj, boot = _encoder_and_boot(src_vocab, word_dim, hidden_dim)
    trg = L.data("trg_word", paddle.data_type.integer_value_sequence(trg_vocab))
    lbl = L.data("trg_next", paddle.data_type.integer_value_sequence(trg_vocab))
    trg_emb = L.embedding(trg, size=word_dim, name="trg_emb")

    step = decoder_step_builder(hidden_dim, trg_vocab, boot)
    dec = L.recurrent_group(
        step,
        [
            trg_emb,
            L.StaticInput(enc, is_seq=True),
            L.StaticInput(enc_proj, is_seq=True),
        ],
        name="decoder",
    )
    cost = L.classification_cost(input=dec, label=lbl, name="nmt_cost")
    return cost, dec


def seq2seq_generation(
    src_vocab: int,
    trg_vocab: int,
    word_dim: int = 128,
    hidden_dim: int = 256,
    bos_id: int = 0,
    eos_id: int = 1,
    beam_size: int = 4,
    max_length: int = 32,
) -> LayerOutput:
    """Generation topology over the SAME step function and layer names as
    :func:`seq2seq_cost`, with the target sequence replaced by a
    GeneratedInput beam (reference demo/seqToseq gen config:
    gen_trans_file + beam_search in seqToseq_net.py).  Because the beam
    layer shares the training group's name ("decoder"), trained parameters
    load via the tar round-trip; copy the target embedding with
    ``gen_params.set("decoder.@gen_emb.w", trained.get("trg_emb.w"))``.

    Build with the same auto-name state as the training topology (e.g. call
    ``paddle_tpu.core.topology.reset_auto_names()`` before each build) so
    the step's internal auto-named layers line up."""
    enc, enc_proj, boot = _encoder_and_boot(src_vocab, word_dim, hidden_dim)
    step = decoder_step_builder(hidden_dim, trg_vocab, boot)
    return L.beam_search(
        step,
        input=[
            L.GeneratedInput(trg_vocab, word_dim),
            L.StaticInput(enc, is_seq=True),
            L.StaticInput(enc_proj, is_seq=True),
        ],
        bos_id=bos_id,
        eos_id=eos_id,
        beam_size=beam_size,
        max_length=max_length,
        name="decoder",
    )


def _subgraph(topo: Topology, names) -> Topology:
    """Rebuild a LayerOutput graph for `names` from an existing Topology and
    return the pruned Topology over just their ancestors."""
    cache = {}

    def build(n: str) -> LayerOutput:
        if n not in cache:
            conf = topo.get(n)
            cache[n] = LayerOutput(conf, [build(p) for p in conf.inputs])
        return cache[n]

    return Topology([build(n) for n in names])


class Seq2SeqGenerator:
    """On-device generation over a trained seq2seq net (capi-style inference
    surface; reference: paddle/gserver/.../RecurrentGradientMachine
    generation mode + demo seqToseq gen configs)."""

    def __init__(
        self,
        parameters: "paddle.parameters.Parameters",
        src_vocab: int,
        trg_vocab: int,
        word_dim: int = 128,
        hidden_dim: int = 256,
        bos_id: int = 0,
        eos_id: int = 1,
        max_length: int = 32,
        beam_size: int = 4,
        candidate_adjust_fn=None,
        drop_fn=None,
        norm_fn=None,
    ):
        self.params = parameters
        self.net = parameters.network
        self.topo = self.net.topology
        self.hidden_dim = hidden_dim
        self.trg_vocab = trg_vocab
        self.bos_id = bos_id
        self.eos_id = eos_id
        self.max_length = max_length
        self.beam_size = beam_size
        # user beam-search control hooks (ops/beam.py module docstring;
        # reference RecurrentGradientMachine.h:70-120 callbacks)
        self.candidate_adjust_fn = candidate_adjust_fn
        self.drop_fn = drop_fn
        self.norm_fn = norm_fn

        dec_conf = self.topo.get("decoder")
        self._sub_topo = dec_conf.attrs["_sub_topology"]
        self._subnet = CompiledNetwork(self._sub_topo)
        self._scan_names = dec_conf.attrs["_scan_placeholders"]
        self._static_info = dec_conf.attrs["_static_placeholders"]
        self._memories = dec_conf.attrs["_memories"]
        # Fused decode stepping: when the decoder step matches the
        # attention-GRU idiom (the same matcher the training scan uses,
        # layers/attention.py), each beam step runs the fused chain
        # (ops/rnn.attention_gru_step) + the vocab head directly instead of
        # interpreting the sub-network layer by layer — in particular the
        # [B*K, S]-row expand+fc state projection collapses to one
        # [B*K, H] GEMM per step.  Structural mismatch -> generic stepping.
        self._match = None
        if len(self._memories) == 1:
            from paddle_tpu.layers.attention import match_attention_gru_step

            m = match_attention_gru_step(
                self._sub_topo.layers,
                self._memories[0],
                set(self._scan_names),
                {p for p, is_seq in self._static_info if is_seq},
            )
            head = self._sub_topo.layers.get("dec_out")
            if (
                m is not None
                and len(m.scan_slots) == 1
                and m.scan_slots[0][1] == self._scan_names[0]
                and head is not None
                and head.type == "fc"
                and head.act == "softmax"
                and head.drop_rate == 0.0
                and tuple(head.inputs) == (m.gru,)
            ):
                self._match = m
        # Pruned encoder-only graph: generation must not pay for the training
        # decoder scan + softmax + cost (and must not require dummy trg slots).
        self._enc_net = CompiledNetwork(
            _subgraph(self.topo, ["enc", "enc_proj", "dec_boot"])
        )

    # -- encoder forward up to the decoder's static inputs ---------------
    def _encode(self, batch, gp):
        outs, _ = self._enc_net.apply(
            gp, batch, state=self.params.state, train=False
        )
        return outs

    def fused_decode_weights(self, gp):
        """Device-ready weight bundle of the fused attention-GRU decode
        step, or None when the decoder step did not match the fused idiom.
        Shared by the beam/greedy stepping here AND the serving plane's
        block-paged decode step (serving/engine.py) — one extraction, one
        numerical contract.  ``gp`` must already be materialized
        (``self.net.materialize_shared``)."""
        if self._match is None:
            return None
        mt = self._match
        sub_params = gp["decoder"]
        lp = lambda n: self._subnet.layer_params(sub_params, n)
        p_in = lp(mt.in_proj)
        p_gru = lp(mt.gru)
        p_sp = lp(mt.state_proj)
        p_head = lp("dec_out")
        bias = sum(p["b"] for p in (p_in, p_gru) if "b" in p)
        return {
            "emb_w": gp["trg_emb"]["w"],
            "w_emb": p_in[f"w{mt.scan_slots[0][0]}"],
            # target-side gate bias (in_proj + gru biases folded); None when
            # both layers are bias-free
            "xg_bias": None if isinstance(bias, int) else bias,
            "w1": jnp.concatenate([p_sp["w0"], p_gru["w_h"]], axis=1),
            "v": lp(mt.scores)["w0"][:, 0],
            "w_ctx": p_in[f"w{mt.ctx_slot}"],
            "w_c": p_gru["w_c"],
            "head_w": p_head["w0"],
            "head_b": p_head.get("b"),
            # state-projection bias folds into the prefill-time score keys
            # (ep = enc_proj + sp_b), NOT into the per-step chain
            "sp_b": p_sp.get("b"),
        }

    def decode_weight_bytes(self, gp=None) -> int:
        """Resident bytes of the fused decode bundle at full precision —
        the f32 baseline of the serving weight-only-int8 capacity math
        (ops.quantize.weight_bundle_bytes measures the quantized side)."""
        if gp is None:
            gp = self.net.materialize_shared(self.params.params)
        w = self.fused_decode_weights(gp)
        if w is None:
            return 0
        from paddle_tpu.ops.quantize import weight_bundle_bytes

        return weight_bundle_bytes(w)

    def _fused(self) -> bool:
        from paddle_tpu.utils.flags import get_flag

        return self._match is not None and bool(get_flag("fused_attention_gru"))

    def _step_fn(self, statics, gp):
        """Build step_fn(ids, carry) for beam/greedy: embeds ids with the
        trained trg_emb table, runs the decoder sub-network once — through
        the fused attention-GRU step when the topology matched."""
        emb_w = gp["trg_emb"]["w"]
        sub_params = gp["decoder"]
        m0 = self._memories[0] if self._memories else None

        if self._fused():
            mt = self._match
            w = self.fused_decode_weights(gp)
            enc_t = statics[mt.enc_name]
            ep = statics[mt.ep_name].data
            if w["sp_b"] is not None:
                ep = ep + w["sp_b"]
            emask = enc_t.mask(bool) if enc_t.lengths is not None else None
            fused = make_fused_step(
                w, enc_t.data, ep, emask,
                gate_act=mt.gate_act, act=mt.act, att_act=mt.att_act,
            )

            def step_fn(ids, carry):
                logp, h_t = fused(ids, carry[m0.name])
                return logp, {m0.name: h_t}

            return step_fn

        def step_fn(ids, carry):
            sub_batch = dict(statics)
            emb = jnp.take(emb_w, ids, axis=0)
            sub_batch[self._scan_names[0]] = SeqTensor(emb)
            for m in self._memories:
                sub_batch[m.name] = SeqTensor(carry[m.name])
            outs, _ = self._subnet.apply(sub_params, sub_batch, train=False)
            new_carry = {m.name: outs[m.attrs["link"]].data for m in self._memories}
            prob = outs["dec_out"].data
            return jnp.log(jnp.maximum(prob, 1e-9)), new_carry

        return step_fn

    def _prepare(self, batch, params=None):
        # materialize once per batch: the pruned encoder net and the decoder
        # sub-network were compiled without the full net's sharing maps, so
        # shared keys (tied embeddings, ...) must be grafted back before
        # either reads params by layer name.  `params` lets a jitted caller
        # pass the weights as an ARGUMENT — jitting a closure over
        # self.params would bake every weight into the jaxpr as a constant
        # (trace-lint rule T102: no donation, re-shipped per compile).
        gp = self.net.materialize_shared(
            self.params.params if params is None else params
        )
        outs = self._encode(batch, gp)
        statics = {}
        static_layers = ["enc", "enc_proj"]
        for (pname, is_seq), lname in zip(self._static_info, static_layers):
            val = outs[lname]
            statics[pname] = val if is_seq else SeqTensor(val.data)
        boot = outs["dec_boot"].data
        if self._fused():
            # the fused chain runs on the master weights, so its state is
            # carried in their dtype — the encoder (under a bfloat16
            # compute dtype) hands over a narrower boot, and a loop carry
            # may not change type.  The serving engine's slot plane holds
            # the state the same way.
            boot = boot.astype(gp["trg_emb"]["w"].dtype)
        carry = {m.name: boot for m in self._memories}
        b = boot.shape[0]
        return statics, carry, b, gp

    def generate(self, batch, beam_size: Optional[int] = None, *, params=None):
        """Beam-search decode; returns (sequences [B,K,T], scores [B,K]).

        ``params`` (default: the constructor's Parameters) exists for jitted
        callers: ``jax.jit(lambda p, bt: gen.generate(bt, params=p))`` keeps
        the weights as executable arguments instead of trace-time constants
        (trace-lint T102)."""
        from paddle_tpu.ops.beam import beam_search

        k = beam_size or self.beam_size
        statics, carry, b, gp = self._prepare(batch, params)
        # static tensors must be expanded to B*K rows inside beam_search —
        # it repeats carry but statics stay per-row: expand here.
        statics_k = {
            n: SeqTensor(
                jnp.repeat(t.data, k, axis=0),
                None if t.lengths is None else jnp.repeat(t.lengths, k, axis=0),
            )
            for n, t in statics.items()
        }
        return beam_search(
            self._step_fn(statics_k, gp),
            carry,
            batch_size=b,
            beam_size=k,
            vocab_size=self.trg_vocab,
            bos_id=self.bos_id,
            eos_id=self.eos_id,
            max_len=self.max_length,
            candidate_adjust_fn=self.candidate_adjust_fn,
            drop_fn=self.drop_fn,
            norm_fn=self.norm_fn,
        )

    def generate_greedy(
        self, batch, *, params=None,
        max_new_tokens: Optional[int] = None, early_exit: bool = True,
    ):
        """Greedy decode; returns ([B, L] ids, [B] lengths) with
        ``L = min(max_length, max_new_tokens)``.

        ``max_new_tokens`` caps the decode per CALL (the constructor's
        ``max_length`` stays the compiled ceiling); ``early_exit`` stops
        stepping once every row has emitted EOS instead of always running
        the full unroll.  Both are BIT-IDENTICAL to the full run truncated:
        finished rows only ever re-emit EOS, and the early-exit buffer is
        EOS-filled, so the [B, L] output arrays match exactly
        (tests/test_seq2seq.py pins this)."""
        from paddle_tpu.ops.beam import greedy_search

        statics, carry, b, gp = self._prepare(batch, params)
        return greedy_search(
            self._step_fn(statics, gp),
            carry,
            batch_size=b,
            bos_id=self.bos_id,
            eos_id=self.eos_id,
            max_len=self.max_length,
            max_new_tokens=max_new_tokens,
            early_exit=early_exit,
        )
