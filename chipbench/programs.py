"""Names by which the trace reduction finds the program's device work:
the jitted serve steps by their jit name as the trace shows it."""

DECODE = "jit_decode"
PREFILL = "jit_prefill"
