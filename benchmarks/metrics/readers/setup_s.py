"""Process start of the command to the first measured step or request."""


def read(obs, params, ctx):
    return obs["setup_s"]
