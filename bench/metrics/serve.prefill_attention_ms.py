"""Device ms of the flash attention kernel (the custom call named
`flash_attention`) per prefill program (the engine's jitted `prefill`),
from the trace."""
from bench import readers


def read(ctx):
    evs = readers.kernel_events(ctx, "flash_attention")
    progs = readers.module_events(ctx, "jit_prefill")
    if not evs or not progs:
        return None
    return 1e3 * sum(e.dur for e in evs) / len(progs)
