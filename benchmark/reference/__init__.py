"""The benchmark's own plain BLS12-381: signs the traffic and checks verdicts."""
