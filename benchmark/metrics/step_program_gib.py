"""Bytes per device the compiled train step needs
(``compiled_train_step().memory_analysis()``), in GiB."""


def read(obs):
    return obs["step_bytes"] / 2**30 if "step_bytes" in obs else None
