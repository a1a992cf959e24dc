"""repro — ACiS (complex processing in the switch fabric) on jax."""
