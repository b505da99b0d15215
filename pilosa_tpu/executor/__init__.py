from .executor import (
    Error,
    ExecOptions,
    Executor,
    FieldRow,
    GroupColumns,
    GroupCount,
    QueryResponse,
    RowIdentifiers,
    ValCount,
)

__all__ = [
    "Error",
    "ExecOptions",
    "Executor",
    "FieldRow",
    "GroupColumns",
    "GroupCount",
    "QueryResponse",
    "RowIdentifiers",
    "ValCount",
]
