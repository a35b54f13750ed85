"""The closed set of ragged-step signatures (tokens, rows, table width)
an engine can reach, from its parameters alone — the rule of
inference/serving.py `_ragged_step`, restated: a step carries every
active sequence's decode token (a rows, a tokens) and up to
`prefill_chunk` prompt tokens over r >= 0 prefill rows; tokens pad to a
power of two (at least 8), rows to a power of two (at most that of
max_batch). A cell's file lists this set and set-up warms exactly it; a
signature outside it would show as window_compiles > 0."""

MIN_Q_TOKENS = 8      # ops/pallas/attention_core.MIN_Q_TOKENS


def pow2(n):
    return 1 << (max(int(n), 1) - 1).bit_length()


def recurrent_closure(max_batch, prefill_chunk):
    """Recurrent (state-slot) strategy: the table width is constant 1.
    Every (decoding rows a, prefilling rows r, prompt tokens p) with
    a + r <= max_batch and r <= p <= prefill_chunk, by enumeration."""
    out = set()
    for a in range(max_batch + 1):
        for r in range(max_batch - a + 1):
            if a + r == 0:
                continue
            for p in ([0] if r == 0 else range(r, prefill_chunk + 1)):
                out.add((max(pow2(a + p), MIN_Q_TOKENS),
                         min(pow2(a + r), pow2(max_batch)), 1))
    return sorted(out)
