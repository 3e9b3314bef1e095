"""repro: TT-decomposition LLM compression on a JAX/Pallas stack."""
