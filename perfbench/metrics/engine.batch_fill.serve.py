"""Share of the tick's [max_slots x prefill_chunk] positions that held a
token the engine was asked for: delta of tokens_prefill + tokens_decode
over ticks x slots x chunk."""


def read(ctx):
    a, b = ctx["marks"]["start"], ctx["marks"]["end"]
    ticks = b["tick"] - a["tick"]
    e = ctx["config"]["engine"]
    toks = (b["tokens_prefill"] + b["tokens_decode"]
            - a["tokens_prefill"] - a["tokens_decode"])
    return (100.0 * toks / (ticks * e["max_slots"] * e["prefill_chunk"])
            if ticks else None)
