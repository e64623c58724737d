"""One number compared with the reference, beside its limit."""

from __future__ import annotations

from typing import NamedTuple


class Check(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit
