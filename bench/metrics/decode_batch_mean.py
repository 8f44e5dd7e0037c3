"""decode_batch_mean: tokens emitted per decode step over the run, from
the engine registry's serve.tokens_generated and serve.decode_steps."""


def read(run):
    steps = run.reg.get("serve.decode_steps", 0)
    if not steps:
        return None
    return run.reg.get("serve.tokens_generated", 0) / steps
