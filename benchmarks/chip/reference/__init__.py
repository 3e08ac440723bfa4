"""Plain references of what the timed paths produce."""
