"""Scope: hierarchical name -> runtime value map.

Reference: /root/reference/paddle/fluid/framework/scope.h:38-81.  Values are
jax Arrays (dense tensors), LoDTensor / SelectedRows / TensorArray wrappers
(core/lod.py), or opaque python objects (readers, rank tables).
"""
from __future__ import annotations

from typing import Dict, Optional


class Scope:
    def __init__(self, parent: Optional["Scope"] = None):
        self.parent = parent
        self._vars: Dict[str, object] = {}
        self.kids = []

    def var(self, name: str):
        """Get-or-create (returns None placeholder if new)."""
        if name not in self._vars:
            s = self._find_scope(name)
            if s is not None:
                return s._vars[name]
            self._vars[name] = None
        return self._vars[name]

    def new_scope(self) -> "Scope":
        s = Scope(self)
        self.kids.append(s)
        return s

    def drop_kids(self):
        self.kids.clear()

    def _find_scope(self, name) -> Optional["Scope"]:
        s = self
        while s is not None:
            if name in s._vars:
                return s
            s = s.parent
        return None

    def find_var(self, name: str):
        s = self._find_scope(name)
        if s is None:
            raise KeyError(f"variable '{name}' not found in scope")
        return s._vars[name]

    def find_vars(self, names):
        """`find_var` for each of `names` in one pass, None where a name
        is not found: what a compiled step asks for its few hundred
        states at every call."""
        if self.parent is None:
            return list(map(self._vars.get, names))
        found = []
        for name in names:
            s = self._find_scope(name)
            found.append(None if s is None else s._vars[name])
        return found

    def has_var(self, name: str) -> bool:
        return self._find_scope(name) is not None

    def set_var(self, name: str, value, local: bool = False):
        """Write `value`.  Non-local writes update the owning scope if the
        name already exists somewhere up the chain (matches executor
        semantics where persistables live in the global scope)."""
        if not local:
            s = self._find_scope(name)
            if s is not None:
                s._vars[name] = value
                return
        self._vars[name] = value

    def local_names(self):
        return list(self._vars.keys())

    def erase(self, name: str):
        self._vars.pop(name, None)
