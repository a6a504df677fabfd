"""A number the driver measured directly with its clock or read from the
device: set-up, initialisation and first-call seconds."""


def read(obs, trace, args):
    return obs.get(args["key"])
