"""Sanity lint gate (the reference CI's cpplint/pylint stage,
Jenkinsfile:31-41, with the linters this image actually has: the
compiler and ast).

Checks, per Python file under the given roots:
  * parses (syntax gate, python3);
  * no tab indentation, no trailing whitespace;
  * lines <= 100 chars (the repo style is ~79 but generated wrappers
    and test tables run long; 100 is the hard wall);
  * no stray debugger invocations left behind;
  * file ends with a newline.
Exit code 1 on any finding.
"""
import ast
import os
import sys

ROOTS = ["mxnet_tpu", "tools", "tests", "example", "docs",
         "__graft_entry__.py"]
MAX_LEN = 100
_PDB = "import " + "pdb"   # split so this file passes its own gate
_BP = "breakpoint" + "("


def lint_file(path):
    problems = []
    try:
        with open(path, encoding="utf-8") as f:
            src = f.read()
    except UnicodeDecodeError as e:
        return ["%s: not utf-8 (%s)" % (path, e)]
    try:
        ast.parse(src, filename=path)
    except SyntaxError as e:
        return ["%s:%s: syntax error: %s" % (path, e.lineno, e.msg)]
    for i, line in enumerate(src.splitlines(), 1):
        stripped = line.rstrip("\n")
        if stripped != stripped.rstrip():
            problems.append("%s:%d: trailing whitespace" % (path, i))
        indent = stripped[:len(stripped) - len(stripped.lstrip())]
        if "\t" in indent:
            problems.append("%s:%d: tab indentation" % (path, i))
        if len(stripped) > MAX_LEN:
            problems.append("%s:%d: line too long (%d > %d)"
                            % (path, i, len(stripped), MAX_LEN))
        if _PDB in stripped or _BP in stripped:
            problems.append("%s:%d: debugger left in" % (path, i))
    if src and not src.endswith("\n"):
        problems.append("%s: missing final newline" % path)
    return problems


def main():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    problems = []
    n_files = 0
    for root in ROOTS:
        full = os.path.join(repo, root)
        if not os.path.exists(full):
            # a vanished root must fail the gate, not pass vacuously
            problems.append("%s: configured lint root missing" % root)
            continue
        if os.path.isfile(full):
            n_files += 1
            problems += lint_file(full)
            continue
        for dirpath, dirnames, files in os.walk(full):
            dirnames[:] = [d for d in dirnames
                           if d not in ("_build", "__pycache__", "data", "_gen")]
            for f in sorted(files):
                if f.endswith(".py"):
                    n_files += 1
                    problems += lint_file(os.path.join(dirpath, f))
    for p in problems:
        print(p)
    print("lint: %d files, %d problems" % (n_files, len(problems)))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
