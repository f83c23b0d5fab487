"""Typed device attributes (reference plugins/shared/structs/attribute.go,
units.go).

A device plugin fingerprints its attributes as typed values with units:
an NVIDIA GPU's memory is the integer 11 in GiB, its clock 1480 in MHz.
A constraint or affinity on `${device.attr.<name>}` compares such a
value with its right-hand side parsed the same way ("2 GiB", "1.4 GHz"):
numbers convert to their unit family's base before they compare, values
of different families (bytes against byte rates) do not compare at all,
and a constraint whose values do not compare fails. Node attributes
(`${attr.*}`) stay strings, as upstream's do.

A node's device group may hold an attribute as an `Attribute`, as a
plain int / float / bool, or as its string form ("11 GiB");
`as_attribute` reads all three the same.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from fractions import Fraction
from functools import lru_cache
from typing import Dict, Optional, Tuple, Union


@dataclass(frozen=True, slots=True)
class Unit:
    name: str
    base: str
    multiplier: int
    inverse: bool = False     # the base is `multiplier` of this unit


def _family(base: str, units) -> list:
    return [Unit(name, base, mult, inv) for name, mult, inv in units]


_K, _KI = 1000, 1 << 10
UNITS: Dict[str, Unit] = {u.name: u for u in (
    _family("B", [("B", 1, False)]
            + [(p + "iB", _KI ** (i + 1), False)
               for i, p in enumerate("KMGTPE")]
            + [("kB", _K, False)]
            + [(p + "B", _K ** (i + 1), False)
               for i, p in enumerate("KMGTPE")])
    + _family("B/s", [("B/s", 1, False)]
              + [(p + "iB/s", _KI ** (i + 1), False)
                 for i, p in enumerate("KMGTPE")]
              + [("kB/s", _K, False)]
              + [(p + "B/s", _K ** (i + 1), False)
                 for i, p in enumerate("KMGTPE")])
    + _family("Hz", [("Hz", 1, False), ("kHz", _K, False),
                     ("MHz", _K ** 2, False), ("GHz", _K ** 3, False)])
    + _family("W", [("mW", _K, True), ("W", 1, False), ("kW", _K, False),
                    ("MW", _K ** 2, False), ("GW", _K ** 3, False)]))}
# longest first, so "GiB/s" is not read as "B/s" behind "GiB"
_BY_LENGTH = sorted(UNITS, key=len, reverse=True)
# Go's strconv forms, not Python's (no "_" digit groups, no blanks)
_INT = re.compile(r"[+-]?[0-9]+")
_FLOAT = re.compile(r"[+-]?([0-9]+\.?[0-9]*|\.[0-9]+)([eE][+-]?[0-9]+)?")
_TRUE = ("1", "t", "T", "TRUE", "true", "True")
_FALSE = ("0", "f", "F", "FALSE", "false", "False")


@dataclass(frozen=True, slots=True)
class Attribute:
    """One typed value: exactly one of int, float, str, bool, and the
    unit of a number ("" for none)."""

    value: Union[int, float, str, bool]
    unit: str = ""

    def __str__(self) -> str:
        if isinstance(self.value, bool):
            return "true" if self.value else "false"
        return f"{self.value} {self.unit}" if self.unit else str(self.value)

    def compare(self, other: "Attribute") -> Tuple[int, bool]:
        """-> (-1 | 0 | 1, comparable). Units first: both or neither,
        and of one family; then a string with a string, a bool with a
        bool, a number with a number (reference Attribute.Compare)."""
        return _compare(type(self.value), self.value, self.unit,
                        type(other.value), other.value, other.unit)


@lru_cache(maxsize=4096)
def _compare(ta, a, unit_a: str, tb, b, unit_b: str) -> Tuple[int, bool]:
    """Attribute.compare on the values and their types (True == 1 as a
    key, so the types are part of it), memoized: a fleet's device
    attributes and its jobs' constraints are few values compared again
    and again, once a placement by the instance choice."""
    mine, theirs = UNITS.get(unit_a), UNITS.get(unit_b)
    if (mine is None) != (theirs is None):
        return 0, False
    if mine is not None and mine.base != theirs.base:
        return 0, False
    if ta is bool or tb is bool:
        if ta is not tb:
            return 0, False
        return (a > b) - (a < b), True
    if ta is str or tb is str:
        if ta is not tb:
            return 0, False
        return (a > b) - (a < b), True
    x, y = _in_base(a, mine), _in_base(b, theirs)
    return (x > y) - (x < y), True


def _in_base(value, unit: Optional[Unit]) -> Fraction:
    """A number in its unit family's base unit, exactly."""
    out = Fraction(value)
    if unit is None:
        return out
    return out / unit.multiplier if unit.inverse else out * unit.multiplier


@lru_cache(maxsize=4096)
def parse_attribute(text: str) -> Attribute:
    """A string as a typed value (reference ParseAttribute): a number
    with a known unit suffix, an int, a float, a bool, else a string."""
    numeric, unit = text, ""
    if text and text[-1].isalpha():
        for name in _BY_LENGTH:
            if text.endswith(name):
                unit, numeric = name, text[:-len(name)].strip()
                break
    if _INT.fullmatch(numeric):
        return Attribute(int(numeric), unit)
    if _FLOAT.fullmatch(numeric):
        return Attribute(float(numeric), unit)
    if text in _TRUE or text in _FALSE:
        return Attribute(text in _TRUE)
    return Attribute(text)


def as_attribute(value) -> Attribute:
    """A device group's attribute as stored (an Attribute, a plain
    number or bool, or its string form) as an Attribute."""
    if isinstance(value, Attribute):
        return value
    if isinstance(value, (bool, int, float)):
        return Attribute(value)
    return parse_attribute(str(value))
