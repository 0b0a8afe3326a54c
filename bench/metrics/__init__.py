"""One reader per metric quantity: ``read(record, arg) -> number | None``."""
