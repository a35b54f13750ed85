"""Speculative decoding through the fixed-shape ragged step
(docs/SERVING.md "Speculative decoding").

A small DRAFT model proposes k tokens per active sequence per
iteration; the TARGET model verifies all k+1 positions as ONE
prefill-chunk-style row through the existing `serve.ragged_step`
executable. The serving kernel already handles mixed prefill/decode
rows, and the MIN_Q_TOKENS=8 token-bucket floor means a k<=7 verify
row pads to the SAME (8, 1, W) signature a 1-token decode row does —
speculation adds zero new executables in steady state
(tools/_gate_common.py enforces this), so the speedup is pure
arithmetic: one cheap draft step per proposed token plus one
target step per k+1 positions, instead of one target step per token.

Why acceptance is an EQUALITY test, not a distribution argument: the
serving sampler keys every draw by fold_in(request_key,
absolute_position) (models/gpt.py sample_token_rows), so the token the
non-speculative engine would emit at a given position is a pure
function of (request seed, history). The verify row reads the target's
per-position sample v_j at every draft position in one step
(paged_ragged_step(return_per_token=True)); `accept_length` then takes
the longest prefix where the draft guessed those exact samples, plus
the first target sample the draft missed. By induction every emitted
token equals the non-speculative stream bit-for-bit — greedy AND
sampled — which is the whole correctness contract (no acceptance-ratio
coin flips, no distribution drift).

Rejected tails roll back the KV write cursor only
(PagedKVCache.rollback): pages, refcounts, and claims are untouched —
the admission claim already reserved worst-case prompt+max_new pages,
and copy-on-write materialized any shared page before the speculated
write, so prefix sharers never observe a rejected token. The draft
model's own PagedKVCache participates in admission as a SECOND claims
ledger (serving.py gates on both pools), so two-model admission can
never double-book either pool.
"""

from ..ops.pallas.attention_core import MIN_Q_TOKENS


class SpeculativeConfig:
    """Configuration handed to GenerationEngine(speculative=...).

    `draft_model` is a smaller model with the SAME tokenizer/vocab as
    the target (typically fewer layers); it runs its own paged cache
    and proposes `k` tokens per sequence per iteration. `k` is capped
    at MIN_Q_TOKENS - 1 so the k+1-token verify row pads into the
    already-warm (MIN_Q_TOKENS, 1, W) ragged signature — a larger k
    would mint a new executable per depth and forfeit the zero-compile
    contract.

    `draft_temperature` optionally overrides the DRAFT's sampling
    temperature (the target's acceptance draw always uses the
    request's own sampling config — this knob only shifts how often
    the draft guesses it).
    None means the draft mirrors each request's own sampling config,
    which maximizes agreement when draft and target logits are close.

    `draft_pages` / `draft_page_size` size the draft model's page pool
    (default: same geometry as the target's)."""

    __slots__ = ("draft_model", "k", "draft_temperature",
                 "draft_pages", "draft_page_size")

    def __init__(self, draft_model, k=4, draft_temperature=None,
                 draft_pages=None, draft_page_size=None):
        k = int(k)
        if not 1 <= k <= MIN_Q_TOKENS - 1:
            raise ValueError(
                f"SpeculativeConfig k={k} out of range [1, "
                f"{MIN_Q_TOKENS - 1}]: the k+1-token verify row must "
                f"fit the MIN_Q_TOKENS={MIN_Q_TOKENS} token bucket or "
                "speculation would mint new executables")
        if draft_model is None:
            raise ValueError("SpeculativeConfig requires a draft model")
        self.draft_model = draft_model
        self.k = k
        self.draft_temperature = (None if draft_temperature is None
                                  else float(draft_temperature))  # hot-sync-ok: construction-time host float, not a device read
        self.draft_pages = draft_pages
        self.draft_page_size = draft_page_size


def accept_length(draft_tokens, verify_samples):
    """Accepted-token count m for one verify row.

    `draft_tokens` is [d_1..d_j] (the j <= k tokens the draft
    proposed); `verify_samples` is [v_0..v_j] (the target's
    position-keyed sample after consuming each of the row's j+1
    tokens, read from the per-token lane of the ragged step).

    m = 1 + the longest prefix where d_{i+1} == v_i: v_0 is
    unconditionally correct (it is sampled from the true history), and
    each subsequent v_i is correct exactly when every earlier draft
    token matched — i.e. when the KV the target wrote for it came from
    the real stream. m == j+1 accepts every draft token AND the bonus
    sample v_j (the draft's reward for a perfect guess: j+1 tokens
    from one target step). The emitted tokens are verify_samples[:m],
    bit-identical to the non-speculative stream by induction."""
    if len(verify_samples) != len(draft_tokens) + 1:
        raise ValueError(
            f"verify_samples has {len(verify_samples)} entries for "
            f"{len(draft_tokens)} draft tokens; expected one per "
            "consumed row token (drafts + the anchor)")
    m = 1
    for d, v in zip(draft_tokens, verify_samples):
        if int(d) != int(v):
            break
        m += 1
    return m
