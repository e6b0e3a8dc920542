"""Reader `convergence`: one fact of the training job's `convergence`
record (EngineInstance.convergence, the last attempt). args: {"key"}."""


def read(args, evidence):
    conv = evidence.get("convergence")
    return None if conv is None else conv.get(args["key"])
