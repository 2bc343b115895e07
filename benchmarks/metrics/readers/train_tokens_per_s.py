"""Tokens trained in the window over the window's seconds (the window is
closed by block_until_ready on the last step), all the cell's chips."""


def read(obs, params, ctx):
    return obs["train"]["tokens"] / obs["window_s"]
