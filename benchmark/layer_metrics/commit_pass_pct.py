"""Share of the diffusion scan's passes that were COMMIT passes (a finished
block run once more, so that the cache keeps the keys of its final tokens; it
delivers nothing): the engine's ``diffusion_commit_passes`` over
``diffusion_passes`` in the window. ``1 / (steps + 1)``: 33.3 at 2 denoising
steps. What folding a commit into the next block's first pass would move.
None for a program that counts no passes."""


def read(run):
    return run["values"].get("commit_pass_pct")
