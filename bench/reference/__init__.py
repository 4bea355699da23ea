"""The benchmark's plain reference of the predictor (see each module)."""
