"""Cross-cutting helpers (reference helper/ — 40 packages; only what we need)."""

from .ids import generate_secret_uuid, generate_uuid, short_id  # noqa: F401
