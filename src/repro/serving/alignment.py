"""Forced-alignment serving head: per-frame emissions -> Viterbi paths.

This is the paper's workload running as a production operator: an acoustic
model upstream of this head produces per-frame state log-posteriors
(B, T, K); a left-to-right HMM over the target transcription's states
constrains the decode; the head returns each request's per-frame alignment.

The head is a thin wrapper around `core.ViterbiDecoder`: the alignment config
resolves to a typed `DecodeSpec` (any batchable spec works — hand one in
directly, or let `core.planner.plan` pick it from a memory budget), the
decoder object owns jit caching, ragged `lengths`, and mesh sharding.
"""

from __future__ import annotations

import dataclasses

from repro.core import (DataPlacement, ViterbiDecoder, as_decode_spec,
                        spec_from_tunables, LexiconConstraint,
                        with_constraint)


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


__all__ = ["AlignmentConfig", "make_alignment_head",
           "make_lexicon_align_head"]
