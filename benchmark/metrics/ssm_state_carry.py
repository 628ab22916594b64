"""The program's step counter ``ssm_state_carry``, mean over the window's
steps: the mean over state-space layers, heads and chunks of ``exp(sum
over the chunk of dt A)``, the share of the state entering a chunk of the
scan that reaches its end. It says how much of the scan's result the
carry between chunks makes (0: none; a state that outlives its chunk
makes more), not how fast anything runs. Left out where the program
has no such counter."""
UNIT, KIND, SOURCE, BETTER = "share", "per_layer", "program_counter", \
    "higher"
LAYER, MOVES = "state-space layer", "train_img_s"


def read(obs):
    return (obs.get("ssm") or {}).get("state_carry")
