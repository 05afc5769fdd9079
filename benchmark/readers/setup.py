"""Process start to window start: imports, boot, loading the fleet,
warm-up, compilation and the rehearsal."""


def read(obs):
    return obs["setup_s"]
