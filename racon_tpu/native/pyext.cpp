// CPython extension wrapper over the native overlap and sequence parsers.
//
// The ctypes route tokenizes a 100 MB PAF in well under a second, but
// materializing ~1.7M per-record Python objects through ctypes costs
// ~4-5 us each — the reconstruction, not the scan, capped ingest at
// ~13 MB/s. Here the field tuples AND the record envelopes are built
// with the direct C API (~0.5 us/record), so the full parse (scan +
// Python objects) sustains >100 MB/s, the reference bioparser's class
// (src/polisher.cpp:83-133).
//
// Records are PyStructSequence instances with attributes (fmt, fields)
// — attribute-compatible with racon_tpu.io.parsers.OverlapRecord, which
// stays the oracle (tests assert field-for-field equality).
//
// Compiled together with parsers.cpp into its own module
// (racon_native_ext.so); racon_tpu.native.parse_ovlfile prefers it and
// falls back to the ctypes path when the extension could not build
// (e.g. no Python headers).

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdint>
#include <cstdlib>

extern "C" int64_t rt_parse_ovlfile(const char* path, int32_t fmt,
                                    char** blob_out, int64_t** soffs_out,
                                    double** nums_out, char* err);
extern "C" int64_t rt_parse_seqfile(const char* path, int32_t is_fastq,
                                    char** blob_out, int64_t** offs_out,
                                    char* err);

namespace {

PyStructSequence_Field kRecFields[] = {
    {"fmt", "overlap format name: 'paf' | 'mhap' | 'sam'"},
    {"fields", "raw field tuple, identical to the Python oracle's"},
    {nullptr, nullptr},
};

PyStructSequence_Desc kRecDesc = {
    "racon_native_ext.OvlRecord",
    "native overlap record (attribute-compatible with "
    "io.parsers.OverlapRecord)",
    kRecFields,
    2,
};

PyTypeObject* g_rec_type = nullptr;
PyObject* g_fmt_names[3] = {nullptr, nullptr, nullptr};
PyObject* g_plus = nullptr;   // cached "+" / "-" strand strings — one
PyObject* g_minus = nullptr;  // allocation per record saved

PyObject* py_parse_ovlfile(PyObject*, PyObject* args) {
    const char* path;
    int fmt;
    if (!PyArg_ParseTuple(args, "si", &path, &fmt)) return nullptr;
    if (fmt < 0 || fmt > 2) {
        PyErr_SetString(PyExc_ValueError, "fmt must be 0 (PAF), 1 (MHAP) "
                                          "or 2 (SAM)");
        return nullptr;
    }
    char* blob = nullptr;
    int64_t* so = nullptr;
    double* nu = nullptr;
    char err[256];
    int64_t n;
    Py_BEGIN_ALLOW_THREADS
    n = rt_parse_ovlfile(path, fmt, &blob, &so, &nu, err);
    Py_END_ALLOW_THREADS
    if (n < 0) {
        PyErr_SetString(PyExc_ValueError, err);
        return nullptr;
    }
    static const int NS[3] = {2, 0, 3};
    const int ns = NS[fmt];
    PyObject* list = PyList_New((Py_ssize_t)n);
    if (!list) goto fail;
    for (int64_t i = 0; i < n; ++i) {
        const int64_t* s = so + 2 * ns * i;
        PyObject* t = nullptr;
        if (fmt == 0) {
            const double* v = nu + 7 * i;
            t = PyTuple_New(9);
            if (!t) goto fail_list;
            int b = (int)v[3];
            char sc = (char)b;
            PyTuple_SET_ITEM(t, 0, PyBytes_FromStringAndSize(
                blob + s[0], (Py_ssize_t)s[1]));
            PyTuple_SET_ITEM(t, 1, PyLong_FromLongLong((long long)v[0]));
            PyTuple_SET_ITEM(t, 2, PyLong_FromLongLong((long long)v[1]));
            PyTuple_SET_ITEM(t, 3, PyLong_FromLongLong((long long)v[2]));
            PyObject* strand;
            if (b == '+') {
                strand = g_plus;
                Py_INCREF(strand);
            } else if (b == '-') {
                strand = g_minus;
                Py_INCREF(strand);
            } else {
                strand = PyUnicode_FromStringAndSize(&sc, b ? 1 : 0);
                if (!strand) {
                    Py_DECREF(t);
                    goto fail_list;
                }
            }
            PyTuple_SET_ITEM(t, 4, strand);
            PyTuple_SET_ITEM(t, 5, PyBytes_FromStringAndSize(
                blob + s[2], (Py_ssize_t)s[3]));
            PyTuple_SET_ITEM(t, 6, PyLong_FromLongLong((long long)v[4]));
            PyTuple_SET_ITEM(t, 7, PyLong_FromLongLong((long long)v[5]));
            PyTuple_SET_ITEM(t, 8, PyLong_FromLongLong((long long)v[6]));
        } else if (fmt == 1) {
            const double* v = nu + 12 * i;
            t = PyTuple_New(12);
            if (!t) goto fail_list;
            for (int k = 0; k < 12; ++k) {
                PyTuple_SET_ITEM(t, k, k == 2
                    ? PyFloat_FromDouble(v[k])
                    : PyLong_FromLongLong((long long)v[k]));
            }
        } else {
            const double* v = nu + 2 * i;
            t = PyTuple_New(5);
            if (!t) goto fail_list;
            PyTuple_SET_ITEM(t, 0, PyBytes_FromStringAndSize(
                blob + s[0], (Py_ssize_t)s[1]));
            PyTuple_SET_ITEM(t, 1, PyLong_FromLongLong((long long)v[0]));
            PyTuple_SET_ITEM(t, 2, PyBytes_FromStringAndSize(
                blob + s[2], (Py_ssize_t)s[3]));
            PyTuple_SET_ITEM(t, 3, PyLong_FromLongLong((long long)v[1]));
            PyTuple_SET_ITEM(t, 4, PyBytes_FromStringAndSize(
                blob + s[4], (Py_ssize_t)s[5]));
        }
        // one check covers every unchecked item allocation above: an
        // allocation failure sets MemoryError and leaves a NULL in the
        // tuple, which tuple_dealloc tolerates (Py_XDECREF)
        if (PyErr_Occurred()) {
            Py_DECREF(t);
            goto fail_list;
        }
        PyObject* rec = PyStructSequence_New(g_rec_type);
        if (!rec) {
            Py_DECREF(t);
            goto fail_list;
        }
        Py_INCREF(g_fmt_names[fmt]);
        PyStructSequence_SET_ITEM(rec, 0, g_fmt_names[fmt]);
        PyStructSequence_SET_ITEM(rec, 1, t);
        PyList_SET_ITEM(list, (Py_ssize_t)i, rec);
    }
    std::free(blob);
    std::free(so);
    std::free(nu);
    return list;
fail_list:
    Py_DECREF(list);
fail:
    std::free(blob);
    std::free(so);
    std::free(nu);
    return nullptr;
}

// parse_seqfile(path, is_fastq) -> list of (name, data, quality | None)
// bytes tuples: what native.parse_seqfile builds through ctypes at 3 us
// a record (three string_at calls), built here at a tenth of that — a
// 50x short-read set is 345,000 records per draft Mbp.
PyObject* py_parse_seqfile(PyObject*, PyObject* args) {
    const char* path;
    int is_fastq;
    if (!PyArg_ParseTuple(args, "sp", &path, &is_fastq)) return nullptr;
    char* blob = nullptr;
    int64_t* offs = nullptr;
    char err[256];
    int64_t n;
    Py_BEGIN_ALLOW_THREADS
    n = rt_parse_seqfile(path, is_fastq, &blob, &offs, err);
    Py_END_ALLOW_THREADS
    if (n < 0) {
        PyErr_SetString(PyExc_ValueError, err);
        return nullptr;
    }
    PyObject* list = PyList_New((Py_ssize_t)n);
    for (int64_t i = 0; list && i < n; ++i) {
        const int64_t* o = offs + 6 * i;
        PyObject* t = PyTuple_New(3);
        if (t) {
            PyTuple_SET_ITEM(t, 0, PyBytes_FromStringAndSize(
                blob + o[0], (Py_ssize_t)o[1]));
            PyTuple_SET_ITEM(t, 1, PyBytes_FromStringAndSize(
                blob + o[2], (Py_ssize_t)o[3]));
            if (o[4] >= 0) {
                PyTuple_SET_ITEM(t, 2, PyBytes_FromStringAndSize(
                    blob + o[4], (Py_ssize_t)o[5]));
            } else {
                Py_INCREF(Py_None);
                PyTuple_SET_ITEM(t, 2, Py_None);
            }
        }
        // as in parse_ovlfile: a failed item allocation leaves a NULL
        // in the tuple, which its dealloc tolerates
        if (!t || PyErr_Occurred()) {
            Py_XDECREF(t);
            Py_CLEAR(list);
            break;
        }
        PyList_SET_ITEM(list, (Py_ssize_t)i, t);
    }
    std::free(blob);
    std::free(offs);
    return list;
}

PyMethodDef methods[] = {
    {"parse_seqfile", py_parse_seqfile, METH_VARARGS,
     "parse_seqfile(path, is_fastq) -> list of (name, data, quality | "
     "None) bytes tuples"},
    {"parse_ovlfile", py_parse_ovlfile, METH_VARARGS,
     "parse_ovlfile(path, fmt) -> list of OvlRecord (0=PAF, 1=MHAP, "
     "2=SAM); .fields is identical to the Python oracle's"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "racon_native_ext", nullptr, -1, methods,
    nullptr, nullptr, nullptr, nullptr,
};

}  // namespace

PyMODINIT_FUNC PyInit_racon_native_ext(void) {
    PyObject* m = PyModule_Create(&moduledef);
    if (!m) return nullptr;
    g_rec_type = PyStructSequence_NewType(&kRecDesc);
    if (!g_rec_type) return nullptr;
    g_fmt_names[0] = PyUnicode_InternFromString("paf");
    g_fmt_names[1] = PyUnicode_InternFromString("mhap");
    g_fmt_names[2] = PyUnicode_InternFromString("sam");
    g_plus = PyUnicode_InternFromString("+");
    g_minus = PyUnicode_InternFromString("-");
    Py_INCREF((PyObject*)g_rec_type);
    PyModule_AddObject(m, "OvlRecord", (PyObject*)g_rec_type);
    return m;
}
