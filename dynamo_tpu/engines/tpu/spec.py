"""Speculative decoding policy: prompt-lookup (n-gram) proposals + greedy
verify (split from the engine monolith; the engine owns only the hook).

Reference parity: the reference exposes speculative decoding as an engine
flag riding vLLM's implementation (components/src/dynamo/vllm/args.py
speculative config plumbing); here proposals come from a per-sequence
n-gram index over the prompt+generation (prompt-lookup decoding) and
verification is ONE [S, spec_k+1]-token dispatch scoring every position
(llama.forward_paged all_logits). Greedy-only: a tick with sampling /
logprobs / logits-processor requests falls back to the fused decode path.

By construction it wins on extractive/repetitive workloads where
proposals hit and loses on random-token workloads (every miss costs a
dispatch that fused decode would have spent on decode_steps tokens) —
hence the ``tick()`` early-outs that keep the engine on the fused path
whenever nothing proposes. Not measured on this installation (ROADMAP
S7).
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

# Shared with the decode tick so spec-verify dispatches reuse the same
# pow2 table-width buckets (and their compiled programs' shapes). No
# cycle: engine.py imports this module lazily (the _spec property).
from dynamo_tpu.engines.tpu.engine import table_width_bucket


class NgramSpecDecoder:
    """Engine-attached speculative decoder (state lives on the sequences;
    the device program lives in the runner)."""

    def __init__(self, engine: Any) -> None:
        self.e = engine

    def propose(self, seq: Any) -> List[int]:
        """Prompt-lookup proposal: index new tokens, then continue from the
        most recent earlier occurrence of the trailing n-gram."""
        n = self.e.args.spec_ngram
        toks = seq.all_tokens
        # Incremental index: register every n-gram ENDING at p, excluding
        # the final position (its continuation is what we're predicting).
        for p in range(max(seq.ngram_upto, n - 1), len(toks) - 1):
            seq.ngram_index[tuple(toks[p - n + 1 : p + 1])] = p + 1
        seq.ngram_upto = max(len(toks) - 1, 0)
        if len(toks) < n:
            return []
        cont = seq.ngram_index.get(tuple(toks[-n:]))
        if cont is None:
            return []
        return toks[cont : cont + self.e.args.spec_k]

    def eligible(self, active: List[Any]) -> bool:
        """Sampled requests are served by the rejection-sampling verify
        (ops/sampling.spec_verify_sample — exact target distribution), so
        temperature no longer gates a tick. Logprobs and logits-processor
        rows still fall back to the fused decode path (the verify program
        surfaces neither per-token logprobs nor processor state)."""
        for s in active:
            sp = s.request.sampling
            if sp.logprobs is not None:
                return False
            if self.e._uses_procs[s.slot]:
                return False
        return True

    async def tick(self) -> bool:
        """One verify dispatch over [next_token + proposals]. Returns False
        when this tick is ineligible or nothing proposes — the fused
        decode_steps-per-dispatch path wins whenever speculation has no
        candidates (a 1-token verify would cost decode_steps× the
        dispatches)."""
        e = self.e
        args = e.args
        # Drain the pipelined decode window first: proposals index
        # all_tokens and the verify dispatch reads/writes host-visible
        # pos/tables, so the spec tick must see fully-reconciled state
        # (and must not interleave with a device burst whose carry it
        # would invalidate). The spec dispatch itself bypasses the
        # device-resident carry — the slots it advances are re-synced via
        # the dirty marks below.
        await e._drain_inflight()
        occupied = [s for s in e._slots if s is not None]
        if not occupied:
            return True
        if not self.eligible(occupied):
            return False
        proposals: Dict[int, List[int]] = {
            s.slot: self.propose(s) for s in occupied
        }
        if not any(proposals.values()):
            return False

        C = args.spec_k + 1
        active = e._prepare_decode(C)
        if not active:
            return True
        S = args.max_num_seqs
        tokens = np.zeros((S, C), dtype=np.int32)
        lens = np.zeros(S, dtype=np.int32)
        max_blocks = 1
        for seq in active:
            slot = seq.slot
            prop = proposals.get(slot, [])
            # Never speculate past the model-length cap.
            room = args.max_model_len - int(e._pos[slot]) - 1
            prop = prop[: max(min(len(prop), room), 0)]
            proposals[slot] = prop
            tokens[slot, 0] = seq.next_token
            tokens[slot, 1 : 1 + len(prop)] = prop
            lens[slot] = 1 + len(prop)
            max_blocks = max(
                max_blocks,
                (int(e._pos[slot]) + C - 1) // args.block_size + 1,
            )
        nb_bucket = table_width_bucket(max_blocks, args.max_blocks_per_seq)

        # The verify dispatch is synchronous: its await is this tick's
        # device wait, and the emission below its host half.
        with e.step_metrics.phase(
            "tick.decode_wait", rows=len(active), nb=nb_bucket
        ):
            emitted_all, counts = await e._device(
                e._run_spec,
                tokens,
                e._pos.copy(),
                lens,
                e._block_tables[:, :nb_bucket].copy(),
                e._adapter_ids.copy(),
                e._temp.copy(),
                e._topk.copy(),
                e._topp.copy(),
            )
        e.steps += 1
        with e.step_metrics.phase("tick.emit", rows=len(active)):
            rows = 0
            for seq in list(active):
                if seq.slot < 0:
                    continue  # finished by an earlier emit in this loop
                rows += 1
                slot = seq.slot
                prop = proposals.get(slot, [])
                n = int(counts[slot])
                emitted = emitted_all[slot, :n].astype(np.int32)
                e.spec_proposed += len(prop)
                e.spec_accepted += n - 1
                e._emit_burst(
                    seq, emitted, np.zeros(n, dtype=np.float32),
                )
                if seq.slot >= 0:
                    # The verify dispatch advanced this slot outside the
                    # decode carry — resync pos/tokens before the next
                    # fused decode burst reads the device-resident state.
                    e._dirty_state.add(slot)
            if rows:
                e._note_frame(rows)
        return True
