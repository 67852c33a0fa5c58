"""From the instant JAX has the chip to the first measured instant: the
program's imports, weight init, compile or cache load, warm-up and
(serving) the ramp. What comes before it is ``start_to_chip_s``."""


def read(obs):
    return obs["setup_s"]
