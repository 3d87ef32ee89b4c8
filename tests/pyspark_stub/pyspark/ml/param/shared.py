"""pyspark.ml.param machinery subset: Param descriptors declared on the
class with ``Params._dummy()`` parents, per-instance value/default maps,
TypeConverters applied on ``_set``."""

from __future__ import annotations

import uuid
from typing import Any, Dict


class TypeConverters:
    @staticmethod
    def toInt(v) -> int:
        return int(v)

    @staticmethod
    def toFloat(v) -> float:
        return float(v)

    @staticmethod
    def toString(v) -> str:
        return str(v)

    @staticmethod
    def toBoolean(v) -> bool:
        if isinstance(v, bool):
            return v
        raise TypeError(f"Boolean Param requires value of type bool, got {v!r}")

    @staticmethod
    def toList(v) -> list:
        return list(v)

    @staticmethod
    def identity(v):
        return v


class Param:
    def __init__(self, parent, name: str, doc: str, typeConverter=None):
        self.parent = getattr(parent, "uid", parent)
        self.name = name
        self.doc = doc
        self.typeConverter = typeConverter or TypeConverters.identity

    # Value semantics like real pyspark (param.py __eq__/__hash__ on
    # str(parent) + name): maps keyed by Param must survive pickling,
    # where keys are recreated as new objects.
    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Param)
            and self.parent == other.parent
            and self.name == other.name
        )

    def __hash__(self) -> int:
        return hash(f"{self.parent}__{self.name}")

    def __repr__(self) -> str:
        return f"Param({self.parent}__{self.name})"


class Params:
    """Like pyspark, the value maps (`_paramMap` / `_defaultParamMap`) are
    keyed by the Param OBJECTS, not by name — consumers such as
    persistence writers iterate `p.name for p in map`.

    Pinned to pyspark 3.5 ``pyspark/ml/param/__init__.py`` semantics:
    ``Params.__init__`` COPIES every class-level Param
    onto the instance with ``parent = self.uid`` (``_copy_params``), so
    ``TpuPCA().k is not TpuPCA.k`` and ``param.parent == instance.uid`` —
    adapter code that assumed shared class-level Param identity would
    pass a naive stub and break on a real cluster. Param equality stays
    VALUE equality on (parent, name) (pyspark's ``__eq__``/``__hash__``
    on ``str(parent) + name``), which is what makes pickled maps work.
    """

    def __init__(self):
        self.uid = f"{type(self).__name__}_{uuid.uuid4().hex[:12]}"
        self._paramMap: Dict[Param, Any] = {}
        self._defaultParamMap: Dict[Param, Any] = {}
        self._copy_params()

    def _copy_params(self) -> None:
        """pyspark 3.5 Params.__init__ behavior: instance-owned copies of
        the class-level Param declarations (parent = this uid)."""
        for name, cls_param in self._class_params().items():
            setattr(
                self,
                name,
                Param(self, cls_param.name, cls_param.doc, cls_param.typeConverter),
            )

    @classmethod
    def _dummy(cls) -> "Params":
        dummy = object.__new__(Params)
        dummy.uid = "undefined"
        return dummy

    @classmethod
    def _class_params(cls) -> Dict[str, Param]:
        out = {}
        for klass in cls.__mro__:
            for name, value in vars(klass).items():
                if isinstance(value, Param) and name not in out:
                    out[name] = value
        return out

    def _params_by_name(self) -> Dict[str, Param]:
        # Instance-owned params (getattr resolves the per-instance copy).
        return {
            name: getattr(self, name) for name in self._class_params()
        }

    def hasParam(self, name: str) -> bool:
        return name in self._class_params()

    def getParam(self, name: str) -> Param:
        try:
            return self._params_by_name()[name]
        except KeyError as e:
            raise AttributeError(f"no param {name}") from e

    def _shouldOwn(self, param: "Param") -> None:
        """pyspark 3.5 Params._shouldOwn: 'Validates that the input param
        belongs to this Params instance' — parent must equal this uid."""
        if not (param.parent == self.uid and self.hasParam(param.name)):
            raise ValueError(f"Param {param} does not belong to {self.uid}.")

    def _resolveParam(self, param) -> Param:
        """pyspark 3.5 Params._resolveParam: a Param is ownership-checked
        and resolved to the INSTANCE copy; a string goes through
        getParam; anything else is a TypeError."""
        if isinstance(param, Param):
            self._shouldOwn(param)
            return getattr(self, param.name)
        if isinstance(param, str):
            return self.getParam(param)
        raise TypeError(f"Cannot resolve {param!r} as a param.")

    def _resolve(self, param) -> Param:
        return self._resolveParam(param)

    def _resetUid(self, newUid: str) -> "Params":
        """pyspark 3.5 Params._resetUid: 'Changes the uid of this
        instance. This updates both the stored uid and the parent uid of
        params and param maps' — the maps must be REBUILT because Param
        hash/equality include the parent. DefaultParamsReader restores a
        persisted uid through this, never by assigning ``.uid``."""
        newUid = str(newUid)
        self.uid = newUid
        new_default: Dict[Param, Any] = {}
        new_map: Dict[Param, Any] = {}
        for name, param in self._params_by_name().items():
            new_param = Param(self, param.name, param.doc, param.typeConverter)
            if param in self._defaultParamMap:
                new_default[new_param] = self._defaultParamMap[param]
            if param in self._paramMap:
                new_map[new_param] = self._paramMap[param]
            setattr(self, name, new_param)
        self._defaultParamMap = new_default
        self._paramMap = new_map
        return self

    def _set(self, **kwargs) -> "Params":
        for name, value in kwargs.items():
            param = self.getParam(name)
            self._paramMap[param] = param.typeConverter(value)
        return self

    def _setDefault(self, **kwargs) -> "Params":
        for name, value in kwargs.items():
            param = self.getParam(name)
            self._defaultParamMap[param] = param.typeConverter(value)
        return self

    def isSet(self, param) -> bool:
        return self._resolve(param) in self._paramMap

    def isDefined(self, param) -> bool:
        p = self._resolve(param)
        return p in self._paramMap or p in self._defaultParamMap

    def getOrDefault(self, param):
        p = self._resolve(param)
        if p in self._paramMap:
            return self._paramMap[p]
        return self._defaultParamMap[p]
