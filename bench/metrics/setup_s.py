"""Set-up: from the start of the process to the opening of the traffic,
through the deploy of the weights, the platform's build, the cold start
that makes the model live and the warm-up of every shape of the run."""


def read(run):
    return run.setup_s
