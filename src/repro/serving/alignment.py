"""Forced-alignment serving head: encoder emissions -> FLASH-BS Viterbi paths.

This is the paper's workload running as a production operator: hubert-xlarge
produces per-frame class posteriors (B, T, 504); a left-to-right HMM over the
target transcription's states constrains the decode; FLASH-BS (dynamic beam)
returns the per-frame alignment.

The head is a thin wrapper around `core.ViterbiDecoder`: the alignment config
resolves to a typed `DecodeSpec` (any batchable spec works — hand one in
directly, or let `core.planner.plan` pick it from a memory budget), the
decoder object owns jit caching, ragged `lengths`, and mesh sharding.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import (DataPlacement, ViterbiDecoder, as_decode_spec,
                        spec_from_tunables, LexiconConstraint,
                        with_constraint)
from repro.core.hmm import HMM


@dataclasses.dataclass(frozen=True)
class AlignmentConfig:
    """Legacy string-form alignment profile; `to_spec()` is the typed view.

    The batched serving path historically ran with whole-layer vectorisation
    (`lanes=None`), so that is what the conversion pins.
    """
    method: str = "flash_bs"       # flash | flash_bs | vanilla | fused
    beam_width: int = 128
    parallelism: int = 8
    chunk: int = 128

    def to_spec(self):
        # spec_from_tunables drops the fields `method` does not consume —
        # the legacy container always carried all four, so no warning here.
        spec, _ = spec_from_tunables(self.method, dict(
            beam_width=self.beam_width, parallelism=self.parallelism,
            chunk=self.chunk, lanes=None))
        return spec


def make_alignment_head(hmm_log_pi, hmm_log_A, cfg, *,
                        mesh=None, data_axis: str = "data"):
    """Returns align(emissions (B, T, K), lengths=None) -> (paths, scores).

    `cfg` is a `DecodeSpec` (preferred), a `DataPlacement` (a spec with the
    data mesh it is served on) or a legacy `AlignmentConfig`.

    `lengths` (B,) gives each request's true frame count; pad frames run as
    tropical-identity steps inside `viterbi_decode_batch`, so results are
    bit-identical to unbatched decodes of the unpadded payloads (for exact
    methods; FLASH-BS keeps its beam approximation but no pad corruption).
    This is the `decode_batch_fn` contract `BatchScheduler` expects.

    With ``mesh=``, or a placement and no ``mesh=``, the request bucket
    shards over the data axis via `ViterbiDecoder.decode_sharded`, which
    pads non-divisible bucket sizes with length-1 dummy rows and slices
    back — per-request results are unaffected (vmap lanes never interact).
    ``mesh=`` stays the one place a mesh enters; a placement only carries
    its own there, and a placement given with ``mesh=`` is refused.
    """
    if isinstance(cfg, DataPlacement):
        if mesh is not None:
            raise ValueError("give the mesh once: a DataPlacement carries "
                             "its own, so pass no mesh= with it")
        mesh, data_axis = cfg.mesh, cfg.data_axis
    spec = as_decode_spec(cfg)
    dec = ViterbiDecoder(spec, hmm_log_pi, hmm_log_A)

    def align(em, lengths=None):
        if mesh is not None:
            return dec.decode_sharded(em, lengths, mesh=mesh,
                                      data_axis=data_axis)
        return dec.decode_batch(em, lengths)

    align.decoder = dec
    return align


def make_lexicon_align_head(hmm_log_pi, hmm_log_A, words, *, cfg=None,
                            self_loops: bool = True, loop_words: bool = True,
                            mesh=None, data_axis: str = "data"):
    """Lexicon-constrained forced alignment: only lexicon arcs survive.

    `words` is the `LexiconConstraint` vocabulary — a sequence of words, each
    a sequence of pronunciation alternatives, each a state sequence (e.g.
    ``[((0, 1, 2), (0, 3, 2)), ((4, 5),)]``).  The constraint compiles the
    trie's arc set into additive {0, NEG_INF} penalties that every decode
    path fuses into its DP adds, so results are bit-identical to decoding
    the `constrain_inputs`-masked HMM densely — but the planner can also
    price the shrunken live-state set (`constraint.live_states`).

    `cfg` is a `DecodeSpec` or legacy `AlignmentConfig` (default: the
    standard FLASH-BS serving profile); its `constraint` field is replaced.
    Returns the same ``align(emissions, lengths=None)`` callable as
    `make_alignment_head`, with ``align.decoder`` / ``align.constraint``
    attached for introspection.
    """
    constraint = LexiconConstraint(words, self_loops=self_loops,
                                   loop_words=loop_words)
    spec = as_decode_spec(AlignmentConfig() if cfg is None else cfg)
    spec = with_constraint(spec, constraint)
    align = make_alignment_head(hmm_log_pi, hmm_log_A, spec,
                                mesh=mesh, data_axis=data_axis)
    align.constraint = constraint
    return align


def make_e2e_align_step(model, params_treedef_hint, hmm: HMM,
                        cfg, num_classes: int):
    """Encoder forward + log-softmax emissions + Viterbi alignment, one jit.

    The serving step for the hubert cells: batch {"embeds": (B, S, D)} ->
    (paths (B, S), scores (B,)).  `cfg` is a `DecodeSpec` or legacy
    `AlignmentConfig`.
    """
    spec = as_decode_spec(cfg)
    if not spec.jittable:
        raise ValueError(f"{type(spec).__name__} cannot run inside the "
                         f"jitted e2e step; use an offline (jittable) spec")

    def step(params, batch):
        x = batch["embeds"]
        # encoder forward reusing the model's loss-path stack
        from repro.models.transformer import _run_stack
        from repro.models.common import rms_norm
        h, _, _ = _run_stack(model.cfg, params, x.astype(model.cfg.dtype),
                             jnp.arange(x.shape[1]), collect_kv=False)
        h = rms_norm(h, params["ln_out"])
        logits = (h @ params["head"]).astype(jnp.float32)
        em = jax.nn.log_softmax(logits[..., :num_classes], axis=-1)
        return jax.vmap(lambda e: spec.run(hmm.log_pi, hmm.log_A, e))(em)

    return step


__all__ = ["AlignmentConfig", "make_alignment_head",
           "make_lexicon_align_head", "make_e2e_align_step"]
