"""Bytes the larger of the engine's forward programs needs
(``compiled_programs()`` through ``flops.program_bytes``), in GiB: what bounds
the KV pool."""
from benchmark import flops, scopes


def read(obs):
    programs = scopes.compiled_programs(obs)
    return max(map(flops.program_bytes, programs.values())) / 2**30 \
        if programs else None
