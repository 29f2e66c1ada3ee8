"""Tokens a pass of the diffusion scan delivered: the engine's count of tokens
delivered by an unmasking (``diffusion_tokens_unmasked``) over its count of
live (slot, iteration) places (``diffusion_passes``) in the window. ``block /
(steps + 1)`` once the slots are full: 4 / 3 at a block of 4 and 2 denoising
steps. None for a program that counts no passes."""


def read(run):
    return run["values"].get("tokens_per_pass")
