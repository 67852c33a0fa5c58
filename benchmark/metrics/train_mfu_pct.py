"""Model FLOP/s utilisation: trained tokens per second times the FLOPs a
token needs forward and backward (the model family's count; recomputed work not
counted) over chips times the bf16 peak."""


def read(obs):
    t0, t1 = obs["window"]
    tok_s = obs["steps"] * obs["tokens_per_step"] / (t1 - t0)
    family = obs["family"]
    per_tok = family.train_flops_per_token(
        family.arch(obs["config"]), obs["seq_len"])
    return 100.0 * tok_s * per_tok / (
        obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
