"""Process start to the window's start: backend, model, server or step,
warm-up, compile or cache load."""


def read(obs):
    return obs["setup_s"]
