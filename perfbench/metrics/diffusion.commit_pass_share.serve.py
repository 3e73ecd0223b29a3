"""Block rows that were commit passes, in %: what the engine's
``stats()["diffusion"]["commit_passes"]`` grew by between the window's marks
over the growth of ``slot_passes``.  A commit pass runs a full block once
more to leave its keys and values in the pool and yields no token: with n
denoising passes a block the share is 1 / (1 + n), what a tick spends
yielding nothing (fusing it with the next block's first pass would take it
to 0).  None where the program counts no such thing (the parent commit, a
model that decodes a token after another) or no block row ran."""


def read(ctx):
    a, b = (ctx["marks"][k]["stats"].get("diffusion")
            for k in ("start", "end"))
    rows = b["slot_passes"] - a["slot_passes"] if a and b else 0
    if not rows:
        return None
    return 100.0 * (b["commit_passes"] - a["commit_passes"]) / rows
