"""Model configurations (``<name>.json``) and the plain references they name."""
