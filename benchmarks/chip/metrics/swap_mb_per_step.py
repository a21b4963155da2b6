"""Transfer engine: activation and optimizer-state DMA bytes a step, as the
executor's ``SwapExecStats`` counts them (``dma_bytes + opt_dma_bytes``),
in MB."""


def read(rec, ctx):
    return rec.counters.get("swap_mb_per_step")
